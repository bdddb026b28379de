"""Benchmark: Naive Bayes + KNN throughput on the local chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Workloads (the BASELINE.json north-star configs #1/#2):
- Naive Bayes churn: sufficient-stat training pass + posterior predict pass
  over encoded rows (one-hot einsum contractions on the MXU).
- KNN elearn-shaped, two configs: d=8 (the reference's feature width —
  memory/VPU-bound by construction at 8 MACs = 16 FLOPs per distance) and
  d=128 (the euclidean-as-matmul regime where MFU is meaningful), both
  through the lane-resident packed-key pallas kernel
  (ops/pallas_knn.knn_topk_lanes) in bfloat16 — the opt-in fast path
  (NeighborIndex(packed=True)); the model-layer default is the exact
  kernel.

Timing methodology: every measurement runs M steps inside ONE jitted
lax.map — each step on distinct data (an on-device roll) — reduces to a
scalar, and fetches it with float(), so the clock stops only when the
device has finished and per-dispatch overhead is amortized over M steps.

Runs on an accelerator only, every section in this one process (a chip
belongs to one process at a time); without a chip it exits non-zero.
`python bench.py [section ...]` runs a subset.

vs_baseline: the reference publishes no numbers (BASELINE.md); the
north-star target is >=50x a 32-node Hadoop cluster on NB+KNN. The two
workloads have very different per-row cost, so vs_baseline is the geometric
mean of per-workload speedups against documented per-workload estimates of
the 32-node Hadoop reference:
- NB scan: 1.0e6 rows/sec (32 nodes x ~31k rows/sec/node; generous for
  MR with an HDFS round trip per job).
- KNN: sifarish SameTypeSimilarity computes all pair distances in JVM text
  records; assume 1e6 pair-distances/sec/node = 3.2e7 pairs/sec for 32
  nodes; at this bench's corpus size (KNN_TRAIN) that is
  3.2e7 / KNN_TRAIN queries/sec (~244 q/s), evaluated at the d=8 config.
"""

import json
import os
import sys
import time

import numpy as np

HADOOP_NB_ROWS_PER_SEC = 1.0e6
HADOOP_PAIR_DIST_PER_SEC = 3.2e7
HADOOP_SCAN_ROWS_PER_SEC = 1.0e6
# Documented MR-vs-native efficiency: published head-to-head comparisons
# (Pavlo et al., "A Comparison of Approaches to Large-Scale Data
# Analysis", SIGMOD 2009; Anderson & Tucek, "Efficiency Matters!", HotOS
# 2009 line of work) place Hadoop per-node scan/grep throughput at or
# below ~10% of a hand-coded native scan on the same hardware (JVM Text
# decode, Writable churn, spill/merge, HDFS replication, task startup).
# measure_baseline_anchor() measures the native rate HERE and scales by
# this factor to obtain a defensible per-node Hadoop rate.
MR_EFFICIENCY = 0.10

NB_ROWS = 1_000_000
NB_STEPS = 8
STREAM_ROWS = 1_000_000_000
STREAM_CHUNK = 8_000_000
# on-disk CSV section size; AVENIR_BENCH_CSV_ROWS overrides (the 1e9-row
# end-to-end run — ~38GB on disk — is recorded one-off via this knob so
# the routine bench stays ~40min; see STREAM_SCALE_r05.json)
STREAM_CSV_ROWS = max(100_000, int(os.environ.get(
    "AVENIR_BENCH_CSV_ROWS", 100_000_000)) // 100_000 * 100_000)
STREAM_CSV_CACHE = f"/tmp/avenir_bench_stream_{STREAM_CSV_ROWS // 10**6}m.csv"
# block must respect the lane kernel's corpus cap (pack_bits <= 12 ->
# <= 524,288 rows per kernel call) and block_t alignment
KNN_STREAM_BLOCK = 1 << 19
KNN_STREAM_TRAIN = 1908 * KNN_STREAM_BLOCK  # 1,000,341,504 rows (>= 1e9)
KNN_STREAM_QUERIES = 512
KNN_STREAM_DIM = 128
# on-disk KNN train corpus (d=128 floats, ~965MB/M rows): real rows,
# no rotation proxy; AVENIR_BENCH_KNN_CSV_ROWS overrides
KNN_CSV_ROWS = max(100_000, int(os.environ.get(
    "AVENIR_BENCH_KNN_CSV_ROWS", 2_000_000)) // 100_000 * 100_000)
KNN_CSV_CACHE = f"/tmp/avenir_bench_knn_{KNN_CSV_ROWS}.csv"


def _cached_replicated_csv(path: str, total_rows: int, make_blob) -> None:
    """Ensure `path` holds total_rows CSV rows: make_blob() returns a
    100K-row blob that is replicated to the target size, validated by a
    rows+size sidecar marker so a warm run skips generation entirely."""
    marker = path + ".rows"
    try:
        with open(marker) as fh:
            if fh.read().strip() == f"{total_rows},{os.path.getsize(path)}":
                return
    except OSError:
        pass
    blob = make_blob()
    with open(path + ".tmp", "w") as fh:
        for _ in range(total_rows // 100_000):
            fh.write(blob)
    os.replace(path + ".tmp", path)
    with open(marker, "w") as fh:
        fh.write(f"{total_rows},{os.path.getsize(path)}")
RF_ROWS = 100_000
RF_TREES = 5
RF_DEPTH = 4
APRIORI_VOCAB = 100
APRIORI_TX = 500_000
BANDIT_GROUPS = 1_000_000
BANDIT_ARMS = 10
BANDIT_ROUNDS = 8
KNN_QUERIES = 8_192
KNN_TRAIN = 131_072
KNN_STEPS = 8
KNN_K = 5

# bf16 peak matmul throughput per chip; MFU for f32 work is reported against
# the same number (conservative). A device not in the table is an error.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _timed(many_fn, *args, repeats: int = 3) -> float:
    """Best wall-clock of `repeats` calls of the jitted scalar-reducing
    many_fn; one untimed warmup compiles. Each repeat perturbs the first
    arg by an on-device roll so no (executable, input) pair repeats."""
    import jax
    import jax.numpy as jnp

    _ = float(many_fn(*args))
    best = np.inf
    for s in range(1, repeats + 1):
        shifted = (jnp.roll(args[0], s, axis=-1),) + args[1:]
        t0 = time.perf_counter()
        _ = float(many_fn(*shifted))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_naive_bayes():
    import jax
    import jax.numpy as jnp
    from avenir_tpu.data import generate_churn
    from avenir_tpu.models.naive_bayes import (
        NaiveBayesModel,
        NaiveBayesPredictor,
        _count_batch_kernel,
    )

    base = generate_churn(100_000, seed=1)
    model = NaiveBayesModel.fit(base)
    codes_small, bins = base.feature_codes(model.binned_fields)
    reps = NB_ROWS // len(base)
    codes = np.tile(codes_small, (reps, 1))
    labels = np.tile(base.labels(), reps)
    n = codes.shape[0]
    k, bmax = 2, max(bins)

    codes_d = jnp.asarray(codes)
    labels_d = jnp.asarray(labels)
    w = jnp.ones((n,), jnp.float32)
    x_cont = jnp.zeros((n, 0), jnp.float32)

    @jax.jit
    def train_many(codes_d, labels_d, w):
        def step(i):
            # distinct data per step: on-device roll (cheap copy)
            c = jnp.roll(codes_d, i, axis=0)
            l = jnp.roll(labels_d, i)
            out = _count_batch_kernel(c, l, x_cont, w, k, bmax)
            return sum(jnp.sum(o) for o in jax.tree.leaves(out))
        return jax.lax.map(step, jnp.arange(1, NB_STEPS + 1)).sum()

    train_rps = n * NB_STEPS / _timed(train_many, codes_d, labels_d, w)

    pred = NaiveBayesPredictor(model)

    @jax.jit
    def predict_many(codes_d):
        def step(i):
            c = jnp.roll(codes_d, i, axis=0)
            out = pred._predict(c, x_cont, pred.tables)
            return sum(jnp.sum(o).astype(jnp.float32)
                       for o in jax.tree.leaves(out))
        return jax.lax.map(step, jnp.arange(1, NB_STEPS + 1)).sum()

    predict_rps = n * NB_STEPS / _timed(predict_many, codes_d)

    # a "row processed" = trained on + predicted once
    rps = 1.0 / (1.0 / train_rps + 1.0 / predict_rps)
    return train_rps, predict_rps, rps


def bench_nb_stream():
    """The 1B-row scale path (BASELINE.md north-star definition): NB
    training through the chunked streaming API — NaiveBayesModel.
    accumulate(defer=True) folds per-chunk count tensors on device, with
    automatic f32-exactness flushes — over STREAM_ROWS rows that never
    coexist in memory. Two measurements:

    - 1B-row accumulate rate: chunks generated on device (PRNG) so the
      number isolates the streaming-fold path at the north star's own
      definition (1e9 rows, flat host RSS) from host CSV parse speed.
    - on-disk CSV end-to-end, MEASURED at STREAM_CSV_ROWS=100M real rows
      (a ~3.8GB file generated once, cached at STREAM_CSV_CACHE): the
      file streams through CsvBlockReader + prefetched() into the same
      accumulate loop. The parse uses the native csv_parse_mt path with
      the host's actual core count (this host: 1 core — stripes scale it
      on multi-core hosts, unmeasurable here). Overlap efficiency =
      end-to-end rate / min(parse-only rate, fold-only rate): 1.0 means
      the prefetch thread fully hides the cheaper stage.

    Returns (gen_rows_per_sec, csv_rows_per_sec, csv_parse_rows_per_sec,
    overlap_efficiency, peak_rss_mb)."""
    import resource

    import jax
    import jax.numpy as jnp
    from avenir_tpu.core.stream import iter_csv_chunks, prefetched
    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.models.naive_bayes import NaiveBayesModel

    schema = churn_schema()
    model = NaiveBayesModel.empty(schema)
    bins = model.bins
    k = schema.num_classes()

    # --- device-generated chunks: the 1B-row pass, zero host ingest -----
    # 4 pre-generated chunks cycled across the loop
    @jax.jit
    def gen_chunk(key):
        ks = jax.random.split(key, len(bins) + 1)
        cols = [jax.random.randint(ks[f], (STREAM_CHUNK,), 0, b, jnp.int32)
                for f, b in enumerate(bins)]
        return (jnp.stack(cols, axis=1),
                jax.random.randint(ks[-1], (STREAM_CHUNK,), 0, k, jnp.int32))
    chunks = [gen_chunk(jax.random.PRNGKey(7 + i)) for i in range(4)]
    x_cont = jnp.zeros((STREAM_CHUNK, 0), jnp.float32)
    n_chunks = STREAM_ROWS // STREAM_CHUNK

    # warmup compiles the fold path
    model.accumulate(*chunks[0], x_cont, defer=True)
    model.flush()
    model = NaiveBayesModel.empty(schema)
    t0 = time.perf_counter()
    for i in range(n_chunks):
        codes_d, labels_d = chunks[i % len(chunks)]
        model.accumulate(codes_d, labels_d, x_cont, defer=True)
    model.flush()
    gen_rps = STREAM_ROWS / (time.perf_counter() - t0)
    assert model.class_counts.sum() == STREAM_ROWS

    # --- on-disk CSV end-to-end (parse + prefetch + accumulate) ---------
    # 100M real rows on disk, generated once and cached across runs; the
    # sidecar marker lets a warm run skip blob generation entirely
    path = STREAM_CSV_CACHE
    _cached_replicated_csv(
        path, STREAM_CSV_ROWS,
        lambda: generate_churn(100_000, seed=9, as_csv=True))
    csv_schema = churn_schema()
    # parse-only rate (native csv_parse_mt block parse, no device work)
    t0 = time.perf_counter()
    parsed = sum(len(c) for c in iter_csv_chunks(path, csv_schema))
    parse_rps = parsed / (time.perf_counter() - t0)
    assert parsed == STREAM_CSV_ROWS
    # fold-only rate on the SAME chunk shape the CSV path feeds
    # (cached parsed blocks cycled; includes the per-chunk
    # feature_codes host encode) — the honest denominator for
    # overlap efficiency
    model2 = NaiveBayesModel.empty(csv_schema)
    cached = []
    for ds in iter_csv_chunks(path, csv_schema):
        cached.append(ds)
        if len(cached) >= 4:
            break
    fold_rows = 0
    t0 = time.perf_counter()
    for i in range(20):
        ds = cached[i % len(cached)]
        codes, _ = ds.feature_codes(model2.binned_fields)
        model2.accumulate(codes, ds.labels(),
                          np.zeros((len(ds), 0), np.float32),
                          defer=True)
        fold_rows += len(ds)
    model2.flush()
    fold_rps = fold_rows / (time.perf_counter() - t0)
    cached = None
    model2 = NaiveBayesModel.empty(csv_schema)
    t0 = time.perf_counter()
    for ds in prefetched(iter_csv_chunks(path, csv_schema)):
        codes, _ = ds.feature_codes(model2.binned_fields)
        model2.accumulate(codes, ds.labels(),
                          np.zeros((len(ds), 0), np.float32),
                          defer=True)
    model2.flush()
    csv_rps = STREAM_CSV_ROWS / (time.perf_counter() - t0)
    assert model2.class_counts.sum() == STREAM_CSV_ROWS
    # perfect parse/fold overlap would run at the slower stage's rate
    overlap_eff = csv_rps / min(parse_rps, fold_rps)
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    return gen_rps, csv_rps, parse_rps, overlap_eff, peak_rss_mb


def bench_knn_stream():
    """KNN at the north star's OWN scale: top-k over a 1-BILLION-row train
    corpus that never exists in memory. A lax.scan of KNN_STREAM_TRAIN /
    KNN_STREAM_BLOCK steps; each step derives its train block from one
    resident [BLOCK, D] tensor by rolling the FEATURE axis (regenerating
    1B rows of PRNG normals would cost more than the distance math and is
    not what the metric measures — note the blocks therefore cycle
    through D distinct feature rotations, a throughput proxy: the
    kernel's cost is data-independent), runs the pallas lane kernel, and
    folds the block's top-k into the running [nq, k] best via a tiny
    argsort merge. Returns (train_rows_per_sec, pair_distances_per_sec,
    elapsed_s)."""
    import jax
    import jax.numpy as jnp
    from avenir_tpu.ops.pallas_knn import knn_topk_lanes

    nq, d, k = KNN_STREAM_QUERIES, KNN_STREAM_DIM, KNN_K
    n_blocks = KNN_STREAM_TRAIN // KNN_STREAM_BLOCK
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))
    t0 = jnp.asarray(rng.normal(
        size=(KNN_STREAM_BLOCK, d)).astype(np.float32))

    @jax.jit
    def sweep(q, t0):
        def step(carry, i):
            best_d, best_i = carry
            t = jnp.roll(t0, i, axis=1)          # feature-rotated block
            dist, idx = knn_topk_lanes(q, t, k=k, block_q=nq, block_t=4096,
                                       metric="euclidean",
                                       compute_dtype="bfloat16")
            gidx = idx + i * KNN_STREAM_BLOCK    # globalize block indices
            d_all = jnp.concatenate([best_d, dist], axis=1)
            i_all = jnp.concatenate([best_i, gidx], axis=1)
            order = jnp.argsort(d_all, axis=1)[:, :k]
            return (jnp.take_along_axis(d_all, order, axis=1),
                    jnp.take_along_axis(i_all, order, axis=1)), None

        init = (jnp.full((nq, k), np.inf, jnp.float32),
                jnp.full((nq, k), -1, jnp.int32))
        (best_d, best_i), _ = jax.lax.scan(step, init,
                                           jnp.arange(n_blocks))
        return jnp.sum(best_d) + jnp.sum(best_i).astype(jnp.float32)

    # AOT compile: executing the full 1B-row sweep just to warm up would
    # double the section's wall clock
    compiled = sweep.lower(q, t0).compile()
    t_start = time.perf_counter()
    _ = float(compiled(q, t0))
    dt = time.perf_counter() - t_start
    return KNN_STREAM_TRAIN / dt, nq * KNN_STREAM_TRAIN / dt, dt


def bench_knn_stream_csv():
    """KNN train-side streaming measured END-TO-END from real on-disk
    rows: a KNN_CSV_ROWS x 128-float CSV (the d=128 bench shape, ~1GB/M
    rows) streams disk -> native parse -> device top-k fold with
    prefetch overlap — no rotation proxy anywhere. This complements
    bench_knn_stream (which prices the 1B-row distance math in
    isolation) with the configuration that exercises the whole sifarish
    replacement: text records in, ranked neighbors out
    (resource/knn.sh:44-57 stage 1).

    Like the NB CSV section, the rate is HOST-PARSE-BOUND at this host's
    single core; the native parser stripes across cores on a real v5e
    host (csv_ingest.cpp, csv_parse_mt). Returns (train_rows_per_sec,
    parse_rows_per_sec, fold_rows_per_sec, overlap_efficiency)."""
    import jax.numpy as jnp
    from avenir_tpu.core.schema import FeatureSchema
    from avenir_tpu.core.stream import iter_csv_chunks, prefetched
    from avenir_tpu.ops.pallas_knn import knn_topk_lanes

    d, nq, k = 128, KNN_STREAM_QUERIES, KNN_K
    step_rows = 131_072                      # device fold granularity
    fields = [{"name": "id", "ordinal": 0, "dataType": "string",
               "id": True}]
    fields += [{"name": f"x{f}", "ordinal": f + 1, "dataType": "double",
                "feature": True} for f in range(d)]
    schema = FeatureSchema.from_json({"fields": fields})

    # on-disk corpus, generated once and cached (100K distinct rows
    # replicated: parse cost is byte-identical for identical rows)
    def make_blob():
        rng = np.random.default_rng(31)
        base = rng.normal(size=(100_000, d)).astype(np.float32)
        return "".join(
            ",".join([str(i)] + [f"{v:.4f}" for v in row]) + "\n"
            for i, row in enumerate(base))

    path = KNN_CSV_CACHE
    _cached_replicated_csv(path, KNN_CSV_ROWS, make_blob)

    rng = np.random.default_rng(32)
    q = jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))

    def block_topk(x, n_valid):
        """x is padded to a multiple of 4096; n_valid masks the padding."""
        return knn_topk_lanes(q, x, k=k, block_q=nq, block_t=4096,
                              metric="euclidean", compute_dtype="bfloat16",
                              n_valid=n_valid)

    def _padded(mat):
        pad = -mat.shape[0] % 4096
        if pad:
            mat = np.concatenate([mat, np.zeros((pad, d), np.float32)],
                                 axis=0)
        return mat

    def fold(chunks):
        """Rebatch parsed chunks into EXACTLY step_rows device folds (so
        the loop uses one compiled shape, plus one for the tail); returns
        (rows, [per-block (dist, global_idx)])."""
        rows, buf, buffered, results = 0, [], 0, []

        def flush(mat, n):
            dist, idx = block_topk(jnp.asarray(_padded(mat)), n)
            results.append((np.asarray(dist), np.asarray(idx) + rows))

        for ds in chunks:
            buf.append(ds.feature_matrix())
            buffered += len(ds)
            while buffered >= step_rows:
                mat = np.concatenate(buf, axis=0)
                flush(mat[:step_rows], step_rows)
                rows += step_rows
                buf, buffered = [mat[step_rows:]], mat.shape[0] - step_rows
        if buffered:
            flush(np.concatenate(buf, axis=0), buffered)
            rows += buffered
        return rows, results

    # warmup compiles both step shapes (full and tail) outside the timing
    tail = KNN_CSV_ROWS % step_rows
    warm = jnp.asarray(np.zeros((step_rows, d), np.float32))
    _ = block_topk(warm, step_rows)
    if tail:
        _ = block_topk(
            jnp.asarray(np.zeros((tail + (-tail % 4096), d), np.float32)),
            tail)
    # parse-only rate (the stage the end-to-end is bound by on 1 core)
    t0 = time.perf_counter()
    parsed = sum(len(c) for c in iter_csv_chunks(path, schema))
    parse_rps = parsed / (time.perf_counter() - t0)
    assert parsed == KNN_CSV_ROWS
    # fold-only rate on the same step shape — the overlap denominator
    # is the SLOWER stage, whichever that is (on a many-core host the
    # striped parse can outrun the fold). Each call gets distinct data
    # (device roll) and the result is forced to host via a scalar, per
    # the module's timing methodology
    rng_f = np.random.default_rng(33)
    fold_block = jnp.asarray(rng_f.normal(
        size=(step_rows, d)).astype(np.float32))
    n_fold = max(4, min(16, KNN_CSV_ROWS // step_rows))
    t0 = time.perf_counter()
    acc = 0.0
    for i in range(n_fold):
        dist, _idx = block_topk(jnp.roll(fold_block, i, axis=1),
                                step_rows)
        acc += float(jnp.sum(dist))
    fold_rps = n_fold * step_rows / (time.perf_counter() - t0)
    assert np.isfinite(acc)
    # end-to-end: parse + prefetch + device top-k fold
    t0 = time.perf_counter()
    rows, results = fold(prefetched(iter_csv_chunks(path, schema)))
    dt = time.perf_counter() - t0
    assert rows == KNN_CSV_ROWS
    # global merge across blocks (tiny: [nq, k*n_blocks])
    d_all = np.concatenate([r[0] for r in results], axis=1)
    i_all = np.concatenate([r[1] for r in results], axis=1)
    order = np.argsort(d_all, axis=1)[:, :k]
    best_i = np.take_along_axis(i_all, order, axis=1)
    assert best_i.shape == (nq, k) and (best_i >= 0).all()
    e2e_rps = rows / dt
    return e2e_rps, parse_rps, fold_rps, e2e_rps / min(parse_rps, fold_rps)


def bench_knn(dim: int, mode: str):
    """One fused classify step (top-k + kernel vote) per query batch.

    Returns (queries/sec, achieved FLOP/s) counting only the 2*nq*nt*d
    distance matmul flops (vote flops are negligible). Uses the
    lane-resident packed kernel (ops/pallas_knn.knn_topk_lanes) in
    bfloat16 — the opt-in fast path (NeighborIndex(packed=True)); the
    model-layer default stays the exact kernel.

    mode: "composed" times the top-k kernel + XLA vote path, "fused" the
    in-kernel vote (knn_classify_lanes)."""
    import jax
    import jax.numpy as jnp
    from avenir_tpu.models.knn import _vote
    from avenir_tpu.ops.pallas_knn import knn_classify_lanes, knn_topk_lanes

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(KNN_QUERIES, dim)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(KNN_TRAIN, dim)).astype(np.float32))
    t_labels = jnp.asarray(rng.integers(0, 2, KNN_TRAIN).astype(np.int32))

    def composed(qi, t, t_labels):
        # lane-resident packed kernel: tile stays in VMEM, carries persist
        # across train blocks, extraction deferred to XLA
        dist, idx = knn_topk_lanes(
            qi, t, k=KNN_K, block_q=1024, block_t=4096,
            metric="euclidean", compute_dtype="bfloat16")
        return _vote(dist, t_labels[idx], jnp.ones_like(dist),
                     "gaussian", 30.0, 2, False, False)

    def fused(qi, t, t_labels):
        return knn_classify_lanes(
            qi, t, t_labels, k=KNN_K, n_classes=2, kernel_fn="gaussian",
            kernel_param=30.0, block_q=1024, block_t=4096,
            metric="euclidean", compute_dtype="bfloat16")

    classify = {"composed": composed, "fused": fused}[mode]

    @jax.jit
    def classify_many(q, t, t_labels):
        def step(i):
            scores = classify(jnp.roll(q, i, axis=0), t, t_labels)
            return jnp.sum(scores).astype(jnp.float32)
        return jax.lax.map(step, jnp.arange(1, KNN_STEPS + 1)).sum()

    dt = _timed(classify_many, q, t, t_labels)
    return (KNN_QUERIES * KNN_STEPS / dt,
            2.0 * KNN_QUERIES * KNN_TRAIN * dim * KNN_STEPS / dt)


def bench_random_forest():
    """North-star config #3 (RF shopping-cart retarget, resource/rafo.properties
    / resource/detr.sh): RandomForestBuilder over the call-hangup dataset.

    The reference's cost unit is one full MR job per tree level
    (detr.sh:34-54 re-runs DecisionTreeBuilder and rotates files per level);
    the metric here is row-level-scans/sec = rows x levels summed over all
    trees, against the same generous HADOOP_SCAN_ROWS_PER_SEC scan-rate
    estimate as NB (each reference level is at best one full scan). Timing
    is wall clock over the whole build — host split-encode, per-level
    jitted histograms, and per-level host sync included (that is the real
    job cost; no scan-amortization trick applies to a host-looped job)."""
    from avenir_tpu.data import generate_call_hangup
    from avenir_tpu.models.tree import RandomForestBuilder

    ds = generate_call_hangup(RF_ROWS, seed=5)
    rf = RandomForestBuilder(ds.schema, num_trees=RF_TREES,
                             max_depth=RF_DEPTH, sampling="withReplace",
                             seed=1)
    rf.fit(ds)  # warmup: compiles the level-histogram kernels
    rf2 = RandomForestBuilder(ds.schema, num_trees=RF_TREES,
                              max_depth=RF_DEPTH, sampling="withReplace",
                              seed=2)
    t0 = time.perf_counter()
    rf2.fit(ds)
    dt = time.perf_counter() - t0
    levels = sum(
        max(len(p.predicates) for p in tree.paths) for tree in rf2.trees
    )
    # model application: the batched device path evaluator vs host loop
    rf2.predict(ds, device=True)  # warmup compiles the path kernel
    t0 = time.perf_counter()
    pred = rf2.predict(ds, device=True)
    predict_rps = RF_ROWS / (time.perf_counter() - t0)
    assert pred.shape == (RF_ROWS,)
    return RF_ROWS * levels / dt, levels, predict_rps


def bench_apriori():
    """North-star config #4 (Apriori association mining, resource/carm.properties
    shape): FrequentItemsApriori over synthetic market-basket transactions
    with enough co-occurrence structure to survive 3 rounds.

    The reference runs one full MR job over ALL transactions per itemset
    length k (FrequentItemsApriori.java:51, driver loop per k); metric =
    transaction-scans/sec = n_transactions x k_rounds, against the same
    scan-rate estimate."""
    from avenir_tpu.models.association import FrequentItemsApriori, TransactionSet

    rng = np.random.default_rng(4)
    v, n, per = APRIORI_VOCAB, APRIORI_TX, 8
    # zipf-ish popularity so higher-order itemsets stay frequent
    pop = 1.0 / np.arange(1, v + 1)
    pop /= pop.sum()
    multihot = np.zeros((n, v), np.uint8)
    picks = rng.choice(v, size=(n, per), p=pop)
    multihot[np.arange(n)[:, None], picks] = 1
    tx = TransactionSet(multihot, [f"i{j}" for j in range(v)],
                        np.array([str(i) for i in range(n)], dtype=object))
    miner = FrequentItemsApriori(support_threshold=0.02, max_length=3)
    miner.mine(tx)  # warmup
    t0 = time.perf_counter()
    lists = miner.mine(tx)
    dt = time.perf_counter() - t0
    rounds = len(lists)
    n_frequent = sum(len(l) for l in lists)
    return n * rounds / dt, rounds, n_frequent


def bench_bandit():
    """North-star config #5 (bandit price optimizer,
    resource/price_optimize_tutorial.txt): one GreedyRandomBandit decision
    round over BANDIT_GROUPS groups x BANDIT_ARMS price levels — the
    map-only per-round MR job (GreedyRandomBandit.java:148-203) as one
    jitted call. Metric = group-decisions/sec across BANDIT_ROUNDS rounds
    (each round fetches its selections, as the job writes them per round)."""
    from avenir_tpu.models.bandits import GreedyRandomBandit, GroupBanditData

    import tempfile

    rng = np.random.default_rng(6)
    g, a = BANDIT_GROUPS, BANDIT_ARMS
    # real group/item ids: the job's cost includes decoding selections and
    # writing per-round rows (GreedyRandomBandit.java:148-203), so the
    # emit path is timed alongside the device select
    group_ids = np.char.add("g", np.arange(g).astype("U8"))
    item_ids = np.broadcast_to(
        np.char.add("p", np.arange(a).astype("U4")), (g, a))
    data = GroupBanditData(
        group_ids=group_ids, item_ids=item_ids,
        counts=rng.integers(0, 50, (g, a)).astype(np.int32),
        rewards=rng.random((g, a)).astype(np.float32) * 100.0,
        mask=np.ones((g, a), bool),
    ).to_device()   # resident round state: one upload, not 3 arrays/round
    bandit = GreedyRandomBandit(batch_size=3, random_selection_prob=0.5,
                                prob_reduction_constant=2.0, seed=3)
    _ = bandit.select(data, 1)  # warmup compile
    t0 = time.perf_counter()
    with tempfile.TemporaryFile("w") as fh:
        for r in range(2, BANDIT_ROUNDS + 2):
            sel = bandit.select(data, r)
            fh.seek(0)
            data.write_selections(np.asarray(sel), fh)
    dt = time.perf_counter() - t0
    assert sel.shape == (g, 3)
    return g * BANDIT_ROUNDS / dt


def measure_baseline_anchor():
    """One MEASURED anchor for the Hadoop-32-node baseline constants.

    The reference publishes no numbers, so vs_baseline has always divided
    by documented estimates (HADOOP_* above). This measures, on this very
    host, a GENEROUS per-node upper bound for each estimate and scales by
    32 nodes, so the companion vs_baseline_measured_anchor figure divides
    by something defensible rather than assumed:

    - nb rows/sec/node: the native C++ single-pass CSV parse+encode rate
      on one core (engine used by Dataset.from_csv). A Hadoop mapper does
      strictly more per row (JVM Text decode, per-field Writable churn,
      spill/merge, HDFS round trip), so one node's whole map pipeline is
      bounded above by one modern core's C parse rate.
    - pair-distances/sec/node: single-process numpy d=8 blocked distance
      rate (C/BLAS). The reference computes each distance from freshly
      split text records in sifarish's JVM inner loop; C-speed floats
      with no parse is again a strict upper bound per node.

    The per-node Hadoop rate is the measured native rate x MR_EFFICIENCY
    (documented <=10% MR-vs-native efficiency — see the constant's
    citation note); the raw measured rates are reported alongside so the
    JSON distinguishes measured from assumed.
    Returns (nb_node_native_rps, pair_node_native_pps)."""
    from avenir_tpu.core.dataset import Dataset
    from avenir_tpu.data import churn_schema, generate_churn

    rows = 200_000
    csv_bytes = generate_churn(rows, seed=23, as_csv=True).encode()
    schema = churn_schema()
    _ = Dataset.from_csv(csv_bytes, schema)         # warm (vocab discovery)
    best = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        Dataset.from_csv(csv_bytes, schema)
        best = min(best, time.perf_counter() - t0)
    nb_node_rps = rows / best

    rng = np.random.default_rng(24)
    q = rng.normal(size=(256, 8)).astype(np.float32)
    t = rng.normal(size=(65_536, 8)).astype(np.float32)
    _ = ((q[:, None, :] - t[None, :256, :]) ** 2).sum(-1)   # warm
    best = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        acc = 0.0
        for s in range(0, t.shape[0], 8_192):
            d2 = ((q[:, None, :] - t[None, s:s + 8_192, :]) ** 2).sum(-1)
            acc += float(d2[0, 0])
        best = min(best, time.perf_counter() - t0)
    pair_node_pps = q.shape[0] * t.shape[0] / best
    return nb_node_rps, pair_node_pps


def bench_knn_matmul_ceiling(dim: int):
    """Measured FLOP/s of a matmul-ONLY pallas kernel at the bench's exact
    tile shapes — the physical ceiling any distance+top-k kernel of this
    shape can reach. At d=128 the [1024,128]@[128,4096] f32-accumulate
    matmul is output-rate-bound on v5e at ~28 TF/s (14% of the 197 TF/s
    bf16 peak, which assumes large contraction depth): identical rates
    measured for the bare XLA dot of the same shape, and K=256/K=512
    XLA dots take the same wall clock (time scales with output elements,
    not flops, until K~1024). MFU-vs-peak is therefore capped by the
    workload shape, not the kernel; the kernel-quality number is
    achieved/ceiling."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    bq, bt = 1024, 4096
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(KNN_QUERIES, dim)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(KNN_TRAIN, dim)).astype(np.float32))

    def kern(q_ref, t_ref, o_ref):
        tb = pl.program_id(1)

        @pl.when(tb == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        dot = jax.lax.dot_general(
            q_ref[...].astype(jnp.bfloat16), t_ref[...].astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[...] += jnp.sum(dot, axis=1, keepdims=True)

    @jax.jit
    def many(q, t):
        def step(i):
            out = pl.pallas_call(
                kern, grid=(KNN_QUERIES // bq, KNN_TRAIN // bt),
                in_specs=[pl.BlockSpec((bq, dim), lambda i, j: (i, 0)),
                          pl.BlockSpec((bt, dim), lambda i, j: (j, 0))],
                out_specs=pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((KNN_QUERIES, 1), jnp.float32),
            )(jnp.roll(q, i, axis=0), t)
            return jnp.sum(out)
        return jax.lax.map(step, jnp.arange(1, KNN_STEPS + 1)).sum()

    dt = _timed(many, q, t)
    return 2.0 * KNN_QUERIES * KNN_TRAIN * dim * KNN_STEPS / dt


def _json_safe(obj):
    """NaN/inf (e.g. a skipped optional section) would emit invalid
    JSON tokens; the driver parses this line, so null them."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def _sec_sanity():
    """Device identity + a timed matmul."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    a = jnp.ones((2048, 2048), jnp.bfloat16)

    @jax.jit
    def mm_many(a):
        def step(x, _):
            return x @ a, None
        out, _ = jax.lax.scan(step, a, None, length=8)
        return jnp.sum(out.astype(jnp.float32))

    _ = float(mm_many(a))
    t0 = time.perf_counter()
    _ = float(mm_many(a))
    return {"device_kind": dev.device_kind, "platform": dev.platform,
            "matmul8_s": round(time.perf_counter() - t0, 4)}


def _sec_nb():
    train_rps, predict_rps, nb_rps = bench_naive_bayes()
    return {"train_rps": train_rps, "predict_rps": predict_rps,
            "nb_rps": nb_rps}


def _sec_knn_d8():
    qps, flops = bench_knn(8, "composed")
    return {"qps": qps, "flops": flops}


def _sec_knn_d128():
    qps, flops = bench_knn(128, "composed")
    return {"qps": qps, "flops": flops}


def _sec_fused_d8():
    return {"fused_qps": bench_knn(8, "fused")[0]}


def _sec_fused_d128():
    return {"fused_qps": bench_knn(128, "fused")[0]}


def _sec_ceiling_d128():
    return {"flops": bench_knn_matmul_ceiling(128)}


def _sec_rf():
    rls, levels, predict_rps = bench_random_forest()
    return {"rls": rls, "levels": levels, "predict_rps": predict_rps}


def _sec_apriori():
    txs, rounds, found = bench_apriori()
    return {"txs": txs, "rounds": rounds, "found": found}


def _sec_bandit():
    return {"gds": bench_bandit()}


def _sec_anchor():
    nb_node_rps, pair_node_pps = measure_baseline_anchor()
    return {"nb_node_rps": nb_node_rps, "pair_node_pps": pair_node_pps}


def _sec_nb_stream():
    gen_rps, csv_rps, parse_rps, overlap_eff, rss_mb = bench_nb_stream()
    return {"gen_rps": gen_rps, "csv_rps": csv_rps, "parse_rps": parse_rps,
            "overlap_eff": overlap_eff, "rss_mb": rss_mb}


def _sec_knn_stream():
    rps, pds, elapsed_s = bench_knn_stream()
    return {"rps": rps, "pds": pds, "elapsed_s": elapsed_s}


def _sec_knn_stream_csv():
    rps, parse_rps, fold_rps, overlap_eff = bench_knn_stream_csv()
    return {"rps": rps, "parse_rps": parse_rps, "fold_rps": fold_rps,
            "overlap_eff": overlap_eff}


def _sec_kernel_sweep():
    """The compiled-kernel sweep (tools/tpu_kernel_check.py), in this
    process: a child could not have the chip this one holds."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import tpu_kernel_check

    passed, total = tpu_kernel_check.run_cases()
    if passed != total:
        raise RuntimeError(f"kernel sweep: {passed}/{total} cases passed")
    return {"passed": passed, "total": total}


# execution order: cheap core metrics first, the two 1B-row streams next
SECTIONS = [
    ("sanity", _sec_sanity),
    ("anchor", _sec_anchor),
    ("nb", _sec_nb),
    ("knn_d8", _sec_knn_d8),
    ("knn_d128", _sec_knn_d128),
    ("ceiling_d128", _sec_ceiling_d128),
    ("rf", _sec_rf),
    ("apriori", _sec_apriori),
    ("bandit", _sec_bandit),
    ("nb_stream", _sec_nb_stream),
    ("knn_stream", _sec_knn_stream),
    ("knn_stream_csv", _sec_knn_stream_csv),
    ("fused_d8", _sec_fused_d8),
    ("fused_d128", _sec_fused_d128),
    ("kernel_sweep", _sec_kernel_sweep),
]


def main(argv):
    """Run the named sections (default: all) in this one process, on an
    accelerator. A section that raises ends the run: its traceback is the
    result."""
    from avenir_tpu.utils.devices import require_backend

    names = argv or [name for name, _fn in SECTIONS]
    fns = dict(SECTIONS)
    unknown = [n for n in names if n not in fns]
    if unknown:
        sys.exit(f"unknown section(s) {unknown}; have {list(fns)}")
    if require_backend() == "cpu":
        sys.exit("bench.py measures the accelerator; JAX_PLATFORMS=cpu "
                 "gives it none (CPU-side instruments: bench_scaling.py)")
    if "sanity" not in names:
        names = ["sanity"] + names       # every line names its device
    results = {}
    for name in names:
        t0 = time.perf_counter()
        results[name] = {"values": fns[name](),
                         "s": round(time.perf_counter() - t0, 1)}
        print(f"# {name} {results[name]['s']}s", file=sys.stderr)
    print(json.dumps(_json_safe(_assemble(results))))


def _bv(results, section, key, default=float("nan")):
    """A section's value; NaN (-> null) for a section this run left out."""
    if section not in results:
        return default
    v = results[section]["values"].get(key, default)
    return default if v is None else v


def _assemble(results: dict) -> dict:
    """Build the one-line bench JSON from this run's section values."""
    device_kind = results["sanity"]["values"]["device_kind"]
    platform = results["sanity"]["values"]["platform"]
    if device_kind not in PEAK_FLOPS:
        raise RuntimeError(
            f"no peak FLOP/s for device_kind {device_kind!r}; add it to "
            "PEAK_FLOPS with its source")
    peak = PEAK_FLOPS[device_kind]
    train_rps = _bv(results, "nb", "train_rps")
    predict_rps = _bv(results, "nb", "predict_rps")
    nb_rps = _bv(results, "nb", "nb_rps")
    stream_rps = _bv(results, "nb_stream", "gen_rps")
    stream_csv_rps = _bv(results, "nb_stream", "csv_rps")
    parse_rps = _bv(results, "nb_stream", "parse_rps")
    overlap_eff = _bv(results, "nb_stream", "overlap_eff")
    rss_mb = _bv(results, "nb_stream", "rss_mb")
    knn_stream_rps = _bv(results, "knn_stream", "rps")
    knn_stream_pds = _bv(results, "knn_stream", "pds")
    knn_stream_s = _bv(results, "knn_stream", "elapsed_s")
    knn_csv_rps = _bv(results, "knn_stream_csv", "rps")
    knn_csv_parse_rps = _bv(results, "knn_stream_csv", "parse_rps")
    knn_csv_fold_rps = _bv(results, "knn_stream_csv", "fold_rps")
    knn_csv_overlap = _bv(results, "knn_stream_csv", "overlap_eff")
    rf_rls = _bv(results, "rf", "rls")
    rf_levels = _bv(results, "rf", "levels")
    rf_predict_rps = _bv(results, "rf", "predict_rps")
    ap_txs = _bv(results, "apriori", "txs")
    ap_rounds = _bv(results, "apriori", "rounds")
    ap_found = _bv(results, "apriori", "found")
    bandit_gds = _bv(results, "bandit", "gds")
    knn_qps = _bv(results, "knn_d8", "qps")
    knn_flops = _bv(results, "knn_d8", "flops")
    knn_qps_hi = _bv(results, "knn_d128", "qps")
    knn_flops_hi = _bv(results, "knn_d128", "flops")
    knn_fused_qps = _bv(results, "fused_d8", "fused_qps")
    knn_fused_qps_hi = _bv(results, "fused_d128", "fused_qps")
    ceiling = _bv(results, "ceiling_d128", "flops")
    anchor_nb_rps = _bv(results, "anchor", "nb_node_rps")
    anchor_pair_pps = _bv(results, "anchor", "pair_node_pps")
    combined = 2.0 / (1.0 / nb_rps + 1.0 / knn_qps)
    nb_speedup = nb_rps / HADOOP_NB_ROWS_PER_SEC
    knn_speedup = knn_qps / (HADOOP_PAIR_DIST_PER_SEC / KNN_TRAIN)
    vs_baseline = float(np.sqrt(nb_speedup * knn_speedup))
    # measured anchor: native per-node rate measured on this host, scaled
    # by the documented MR efficiency factor, x 32 nodes
    anchored_nb_cluster = 32 * MR_EFFICIENCY * anchor_nb_rps
    anchored_pair_cluster = 32 * MR_EFFICIENCY * anchor_pair_pps
    nb_speedup_anchor = nb_rps / anchored_nb_cluster
    knn_speedup_anchor = knn_qps / (anchored_pair_cluster / KNN_TRAIN)
    vs_baseline_anchor = float(np.sqrt(
        nb_speedup_anchor * knn_speedup_anchor))
    # the other three north-star configs, against the same per-scan
    # estimate: the reference pays >= one full MR scan per tree level /
    # per itemset length / per decision round
    rf_speedup = rf_rls / HADOOP_SCAN_ROWS_PER_SEC
    apriori_speedup = ap_txs / HADOOP_SCAN_ROWS_PER_SEC
    bandit_speedup = bandit_gds / HADOOP_SCAN_ROWS_PER_SEC
    vs_baseline_all5 = float(np.prod(
        [nb_speedup, knn_speedup, rf_speedup, apriori_speedup,
         bandit_speedup]) ** 0.2)
    mfu_d8 = knn_flops / peak
    mfu_d128 = knn_flops_hi / peak
    ceiling_frac = knn_flops_hi / ceiling
    print(
        f"# device={device_kind} nb_train={train_rps:.3e} "
        f"nb_predict={predict_rps:.3e} nb={nb_rps:.3e} knn_d8={knn_qps:.3e} "
        f"q/s ({knn_flops/1e12:.1f} TF/s, MFU {mfu_d8*100:.1f}% — d=8 is "
        f"8 MACs (16 FLOPs)/distance, VPU/memory-bound by construction) "
        f"knn_d128={knn_qps_hi:.3e} q/s ({knn_flops_hi/1e12:.1f} TF/s, "
        f"MFU {mfu_d128*100:.1f}%, shape ceiling {ceiling/1e12:.1f} TF/s "
        f"-> {ceiling_frac*100:.0f}% of ceiling) "
        f"nb_speedup={nb_speedup:.1f}x knn_speedup={knn_speedup:.1f}x "
        f"stream1b={stream_rps:.3e} r/s knn1b={knn_stream_rps:.3e} tr/s "
        f"({knn_stream_s:.1f}s) stream_csv={stream_csv_rps:.3e} r/s "
        f"(parse {parse_rps:.3e} r/s) peak_rss={rss_mb:.0f}MB",
        file=sys.stderr,
    )
    out = {
        "metric": "nb_knn_rows_per_sec_per_chip",
        "device": {"platform": platform, "kind": device_kind},
        "value": round(combined, 1),
        "unit": "rows/sec",
        "vs_baseline": round(vs_baseline, 2),
        "vs_baseline_all5_geomean": round(vs_baseline_all5, 2),
        "rf_row_levels_per_sec": round(rf_rls, 1),
        "rf_levels": rf_levels,
        "rf_predict_rows_per_sec": round(rf_predict_rps, 1),
        "rf_speedup": round(rf_speedup, 2),
        "apriori_tx_scans_per_sec": round(ap_txs, 1),
        "apriori_rounds": ap_rounds,
        "apriori_frequent_sets": ap_found,
        "apriori_speedup": round(apriori_speedup, 2),
        "bandit_group_decisions_per_sec": round(bandit_gds, 1),
        "bandit_speedup": round(bandit_speedup, 2),
        "all5_note": ("rf/apriori/bandit measure the remaining north-star "
                      "configs end-to-end (host loop + per-step device "
                      "sync included, no scan amortization); speedups "
                      "divide by the same documented 1e6/sec full-scan "
                      "estimate of the 32-node reference (one MR job per "
                      "tree level / itemset length / decision round)"),
        "nb_rows_per_sec": round(nb_rps, 1),
        "nb_stream_1b_rows_per_sec": round(stream_rps, 1),
        "nb_stream_1b_vs_inmemory": round(stream_rps / train_rps, 3),
        "knn_stream_1b_train_rows_per_sec": round(knn_stream_rps, 1),
        "knn_stream_1b_pair_distances_per_sec": round(knn_stream_pds, 1),
        "knn_stream_1b_elapsed_s": round(knn_stream_s, 2),
        "knn_stream_note": (
            f"top-k over a {KNN_STREAM_TRAIN/1e9:.2f}B-row train corpus "
            f"streamed in {KNN_STREAM_BLOCK/1e3:.0f}K-row blocks "
            f"({KNN_STREAM_QUERIES} queries, d={KNN_STREAM_DIM}, "
            "bf16 pallas lane kernel + running argsort merge; blocks are "
            "feature rotations of one resident block so the metric "
            "prices distance math, not PRNG generation — a throughput "
            "proxy, the kernel cost being data-independent)"),
        "knn_stream_csv_rows_per_sec": round(knn_csv_rps, 1),
        "knn_stream_csv_parse_rows_per_sec": round(knn_csv_parse_rps, 1),
        "knn_stream_csv_fold_rows_per_sec": round(knn_csv_fold_rps, 1),
        "knn_stream_csv_overlap_efficiency": round(knn_csv_overlap, 3),
        "knn_stream_csv_note": (
            f"REAL on-disk end-to-end: {KNN_CSV_ROWS/1e6:.0f}M x 128-float "
            "rows (~"
            f"{KNN_CSV_ROWS*965/1e9:.1f}GB) stream disk -> native parse -> "
            "device top-k fold with prefetch overlap — no rotation proxy; "
            "bound by the slower stage (this run: "
            + ("parse" if not np.isfinite(knn_csv_parse_rps)
               or not np.isfinite(knn_csv_fold_rps)
               or knn_csv_parse_rps <= knn_csv_fold_rps else "fold")
            + f"; the native parser stripes across this host's "
            f"{os.cpu_count()} cores)"),
        "nb_stream_csv_rows_per_sec": round(stream_csv_rps, 1),
        "csv_parse_rows_per_sec": round(parse_rps, 1),
        "csv_overlap_efficiency": round(overlap_eff, 3),
        "peak_rss_mb": round(rss_mb, 1),
        "stream_note": (f"streaming path: {STREAM_ROWS//10**6}M rows folded "
                        "through accumulate(defer=True) in "
                        f"{STREAM_CHUNK//10**6}M-row chunks that never "
                        "coexist in memory (device-generated, isolates the "
                        "fold from host parse); csv figures are MEASURED "
                        f"over {STREAM_CSV_ROWS//10**6}M real on-disk rows "
                        f"(~{STREAM_CSV_ROWS*38/10**9:.1f}GB) through "
                        "CsvBlockReader+prefetched() with "
                        "the native csv_parse_mt at the host's core count "
                        f"({os.cpu_count()}); overlap_efficiency = "
                        "end-to-end / min(parse-only, fold-only) rate"),
        "baseline_note": ("vs_baseline divides by DOCUMENTED ESTIMATES of a "
                          "32-node Hadoop cluster (1.0e6 NB rows/sec, 3.2e7 "
                          "pair-distances/sec — see module docstring), not "
                          "measured reference numbers; the reference "
                          "publishes none (BASELINE.md)"),
        "vs_baseline_measured_anchor": round(vs_baseline_anchor, 2),
        "baseline_anchor": {
            "nb_node_native_rows_per_sec_measured": round(anchor_nb_rps, 1),
            "pair_node_native_distances_per_sec_measured":
                round(anchor_pair_pps, 1),
            "mr_efficiency_factor_assumed": MR_EFFICIENCY,
            "anchored_cluster_nb_rows_per_sec": round(anchored_nb_cluster, 1),
            "anchored_cluster_pair_distances_per_sec":
                round(anchored_pair_cluster, 1),
            "note": ("per-node native scan rates MEASURED on this host "
                     "(single-core C parse+encode; single-process numpy "
                     "d=8 distances), scaled by the documented <=10% "
                     "MR-vs-native efficiency (Pavlo et al. SIGMOD'09 "
                     "line of work — see MR_EFFICIENCY) and 32 nodes; "
                     "only the efficiency factor is assumed, and it is "
                     "generous to Hadoop"),
        },
        "knn_d8_qps": round(knn_qps, 1),
        "knn_d8_fused_classify_qps": round(knn_fused_qps, 1),
        "knn_d128_qps": round(knn_qps_hi, 1),
        "knn_d128_fused_classify_qps": round(knn_fused_qps_hi, 1),
        "fused_note": ("fused = in-kernel label-packed vote "
                       "(knn_classify_lanes): class scores leave the "
                       "kernel instead of (k + hi) * 128 packed key "
                       "lanes, attacking the measured output-rate "
                       "ceiling; composed qps = top-k kernel + XLA vote"),
        "knn_d128_tflops": round(knn_flops_hi / 1e12, 2),
        "knn_d128_mfu": round(mfu_d128, 4),
        "knn_d128_shape_ceiling_tflops": round(ceiling / 1e12, 2),
        "knn_d128_frac_of_ceiling": round(ceiling_frac, 3),
        "peak_tflops": round(peak / 1e12, 1),
        "mfu_note": ("the d=128 distance matmul [*,128]@[128,*] is "
                     "output-rate-bound on v5e: a matmul-ONLY kernel of "
                     "the same shape measures the ceiling above (~14% of "
                     "the large-K bf16 peak); kernel quality = "
                     "frac_of_ceiling"),
        "timing_note": "scan-amortized, scalar-forced timing",
        "scaling_projection_8_to_256": _scaling_projection(train_rps),
        "scaling_projection_note": (
            "weak-scaling efficiency projected from THIS chip's measured "
            "NB step time and the HLO-validated 648B all-reduce payload "
            "(see parallel/scaling.py: 2D-torus dimension-wise collective, "
            "public v5e ICI ballparks); rows give 65k-rows/device bench "
            "steps and the 4M-row streaming-fold steps that amortize hop "
            "latency away"),
        "kernel_sweep_passed": _bv(results, "kernel_sweep", "passed", None),
        "section_seconds": {name: entry["s"]
                            for name, entry in results.items()},
    }
    return out


def _scaling_projection(train_rps: float):
    """Pod-scale projection grounded in the measured single-chip rate."""
    from avenir_tpu.parallel.scaling import (nb_payload_bytes,
                                             project_efficiency)

    if not np.isfinite(train_rps):
        return None
    # the payload the scaling harness validates against the compiled HLO
    payload = nb_payload_bytes()
    return {
        "bench_step_65k_rows": project_efficiency(65_536 / train_rps,
                                                  payload),
        "stream_step_4m_rows": project_efficiency(4_000_000 / train_rps,
                                                  payload),
    }


if __name__ == "__main__":
    main(sys.argv[1:])
