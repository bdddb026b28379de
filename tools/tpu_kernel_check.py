"""Compiled-path sweep of every pallas KNN kernel on the real TPU.

Interpret-mode tests (tests/test_pallas_knn.py) prove the algorithms; this
script proves the Mosaic-compiled artifacts: bitcast/int-key ops, pack-bit
quantization, n_valid masking, sentinel laundering, same-lane collisions,
and both compute dtypes, each checked against a NumPy oracle ON DEVICE.

Usage: python tools/tpu_kernel_check.py   (needs jax.default_backend()=tpu;
anything else is an error, not a skip)
Exit code 0 iff every case passes; prints one summary JSON line.

`run_cases(interpret=True, quick=True)` is the same sweep through the
Pallas interpreter at the small cases only — what chip_smoke.py's CPU dry
run calls; it says nothing about the compiled kernels.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def oracle(q, t, k, metric):
    if metric == "euclidean":
        full = np.sqrt(((q[:, None, :] - t[None, :, :]) ** 2).mean(-1))
    else:
        full = np.abs(q[:, None, :] - t[None, :, :]).sum(-1) / q.shape[1]
    order = np.argsort(full, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(full, order, axis=1), order


def check(name, got_d, got_i, q, t, k, metric, rtol):
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)
    ref_d, ref_i = oracle(q, t, k, metric)
    kk = min(k, t.shape[0])
    ok = True
    msg = []
    if not np.allclose(got_d[:, :kk], ref_d[:, :kk], rtol=rtol, atol=1e-5):
        ok = False
        msg.append(f"dist err {np.abs(got_d[:, :kk]-ref_d[:, :kk]).max():.2e}")
    # tie-tolerant recall: a returned neighbor counts if its TRUE distance
    # is within the mode's quantization tolerance of the kth-best — the
    # packed/bf16 modes may legally swap near-ties
    if metric == "euclidean":
        full = np.sqrt(((q[:, None, :] - t[None, :, :]) ** 2).mean(-1))
    else:
        full = np.abs(q[:, None, :] - t[None, :, :]).sum(-1) / q.shape[1]
    hits = 0
    for r in range(q.shape[0]):
        bar = ref_d[r, kk - 1] * (1.0 + 2 * rtol) + 1e-6
        hits += sum(full[r, i] <= bar for i in got_i[r, :kk] if i >= 0) / kk
    recall = hits / q.shape[0]
    if recall < 0.999:
        ok = False
        msg.append(f"tie-tolerant recall {recall:.3f}")
    if kk < k and not (np.isinf(got_d[:, kk:]).all()
                       and (got_i[:, kk:] == -1).all()):
        ok = False
        msg.append("bad sentinel slots")
    if (got_i[:, :kk] >= t.shape[0]).any() or (got_i[:, :kk] < 0).any():
        ok = False
        msg.append("index out of range")
    print(f"{'PASS' if ok else 'FAIL'} {name}" + (": " + "; ".join(msg) if msg else ""))
    return ok


def exact_at_cells_shape(kw, quick):
    """The exact kernel at the benchmark's kNN cells' shape (manhattan over
    nine whole-number activity fields as shares of their ranges, k=5, tiles
    of 256 x 8,192, a padded tail), its (summed distance, index) held bit
    for bit to the k least pairs in lexicographic order. The oracle adds
    the features in the kernel's order in float32, so the sums are the same
    bits; whole numbers make ties by the thousand, so the order among them
    is tested too. Prints the share of slices the kernel extracted."""
    import jax.numpy as jnp
    from avenir_tpu.ops.distance import pad_train
    from avenir_tpu.ops.pallas_knn import knn_topk_pallas, slice_rows

    bq, bt, d, k = 256, 8192, 9, 5
    nq, nt = (256, 2 * bt - 1000) if quick else (512, 128 * bt - 1000)
    rng = np.random.default_rng([7, nt, d])
    hi = np.array([600, 200, 100, 28, 100, 100, 280, 180, 26], np.float32)

    def draw(n):
        x = np.clip(rng.normal(0.5, 0.12, (n, d)) * hi, 0, hi)
        return x.astype(np.int32).astype(np.float32) / hi

    q, t = draw(nq), draw(nt)
    t_pad, _, n_valid = pad_train(t, None, bt)
    got_d, got_i, ext = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=bq, block_t=bt,
        metric="manhattan", n_valid=n_valid, n_attrs=1, **kw)
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)
    ok = True
    rows = np.arange(bq, dtype=np.int32)
    for b in range(0, nq, bq):
        acc = np.zeros((bq, nt), np.float32)
        for f in range(d):
            acc += np.abs(q[b:b + bq, f][:, None] - t[:, f][None, :])
        for j in range(k):      # the least pair left: argmin takes the first
            near = acc.argmin(axis=1)
            ok = ok and np.array_equal(got_i[b:b + bq, j], near) \
                and np.array_equal(got_d[b:b + bq, j], acc[rows, near])
            acc[rows, near] = np.inf
    slices = (nq // bq) * (t_pad.shape[0] // slice_rows(bt))
    ext = int(np.asarray(ext).sum())
    print(f"{'PASS' if ok else 'FAIL'} exact/cells-shape {nq} x {nt} "
          f"(slices of {slice_rows(bt)} rows: {ext} of {slices} extracted)"
          + ("" if ok else ": not the k least (distance, index) pairs"))
    return ok


def run_cases(interpret: bool = False, quick: bool = False):
    """(passed, total) over every case; one PASS/FAIL line each."""
    import jax
    import jax.numpy as jnp
    from avenir_tpu.ops.distance import pad_train
    from avenir_tpu.ops.pallas_knn import knn_topk_lanes, knn_topk_pallas

    kw = {"interpret": interpret}
    rng = np.random.default_rng(7)
    results = []

    cases = [
        # (label, nq, nt_real, d, k, block_q, block_t, metric)
        ("basic", 256, 4096, 16, 5, 256, 512, "euclidean"),
        ("pad", 256, 3000, 16, 5, 256, 512, "euclidean"),
        ("multiblock", 256, 16384, 32, 5, 256, 2048, "euclidean"),
        ("tiny_train", 128, 3, 8, 5, 128, 256, "euclidean"),
        ("k1", 128, 2048, 8, 1, 128, 512, "euclidean"),
        ("manhattan", 128, 1024, 8, 4, 128, 512, "manhattan"),
    ]
    if quick:
        cases = [c for c in cases if c[2] <= 4096]
    for label, nq, nt, d, k, bq, bt, metric in cases:
        rng = np.random.default_rng([7, nt, d])   # per case: quick == full
        q = rng.normal(size=(nq, d)).astype(np.float32)
        t = rng.normal(size=(nt, d)).astype(np.float32)
        t_pad, _, n_valid = pad_train(t, None, bt)
        qd, td = jnp.asarray(q), jnp.asarray(t_pad)

        de, ie, _ = knn_topk_pallas(qd, td, k=k, block_q=bq, block_t=bt,
                                 metric=metric, n_valid=n_valid, **kw)
        results.append(check(f"exact/{label}", de, ie, q, t, k, metric, 1e-3))
        if bt <= 4096:
            dp, ip, _ = knn_topk_pallas(qd, td, k=k, block_q=bq, block_t=bt,
                                     metric=metric, n_valid=n_valid,
                                     packed=True, **kw)
            results.append(
                check(f"packed/{label}", dp, ip, q, t, k, metric, 3e-3))
        dl, il = knn_topk_lanes(qd, td, k=k, block_q=bq, block_t=bt,
                                metric=metric, n_valid=n_valid, **kw)
        results.append(check(f"lanes/{label}", dl, il, q, t, k, metric, 3e-3))
        if metric == "euclidean":
            db, ib = knn_topk_lanes(qd, td, k=k, block_q=bq, block_t=bt,
                                    metric=metric, n_valid=n_valid,
                                    compute_dtype="bfloat16", **kw)
            # bf16 cross term: ~2^-8 relative on distances
            results.append(
                check(f"lanes-bf16/{label}", db, ib, q, t, k, metric, 2e-2))

    # fused in-kernel vote vs composed top-k + _vote, compiled
    from avenir_tpu.models.knn import _vote
    from avenir_tpu.ops.pallas_knn import knn_classify_lanes

    for kernel_fn, metric in (("none", "euclidean"), ("gaussian", "euclidean"),
                              ("linearAdditive", "manhattan")):
        nq, d, k, C = 256, 8, 5, 3
        q = rng.normal(size=(nq, d)).astype(np.float32)
        t = rng.normal(size=(3000, d)).astype(np.float32)
        labels = rng.integers(0, C, 3000).astype(np.int32)
        t_pad, _, n_valid = pad_train(t, None, 512)
        lab_pad = np.zeros(t_pad.shape[0], np.int32)
        lab_pad[:3000] = labels
        scores = np.asarray(knn_classify_lanes(
            jnp.asarray(q), jnp.asarray(t_pad), jnp.asarray(lab_pad), k=k,
            n_classes=C, kernel_fn=kernel_fn, kernel_param=30.0, block_q=256,
            block_t=512, metric=metric, n_valid=n_valid, **kw))
        dist, idx = knn_topk_lanes(jnp.asarray(q), jnp.asarray(t_pad), k=k,
                                   block_q=256, block_t=512, metric=metric,
                                   n_valid=n_valid, **kw)
        ref = np.asarray(_vote(dist, jnp.asarray(lab_pad)[jnp.maximum(idx, 0)],
                               jnp.ones_like(dist), kernel_fn, 30.0, C,
                               False, False))
        agree = float((scores.argmax(1) == ref.argmax(1)).mean())
        ok = agree >= 0.99 and np.abs(scores - ref).max() <= 2.0
        print(f"{'PASS' if ok else 'FAIL'} fused-vote/{kernel_fn}-{metric}"
              + ("" if ok else f": agree={agree:.3f}"))
        results.append(ok)

    # exhausted-rounds edge: a corpus smaller than k forces the epilogue
    # through the int32-max fill (whose label-masked bits bitcast to NaN);
    # with a non-'none' kernel the scores must stay finite and the vote
    # mass must equal the real-neighbor count (regression for the
    # duplicate-count extraction fix)
    for dtype in ("float32", "bfloat16"):
        q = rng.normal(size=(256, 4)).astype(np.float32)
        t3 = rng.normal(size=(3, 4)).astype(np.float32)
        lab3 = np.array([0, 1, 1], np.int32)
        t_pad, _, n_valid = pad_train(t3, None, 512)
        lab_pad = np.zeros(t_pad.shape[0], np.int32)
        lab_pad[:3] = lab3
        scores = np.asarray(knn_classify_lanes(
            jnp.asarray(q), jnp.asarray(t_pad), jnp.asarray(lab_pad), k=5,
            n_classes=2, kernel_fn="gaussian", kernel_param=30.0,
            block_q=256, block_t=512, n_valid=n_valid,
            compute_dtype=dtype, **kw))
        ok = bool(np.isfinite(scores).all())
        print(f"{'PASS' if ok else 'FAIL'} fused-vote-exhausted/{dtype}"
              + ("" if ok else ": non-finite scores"))
        results.append(ok)

    # mixed categorical data through the one-hot expansion, compiled
    from avenir_tpu.models.knn import _expand_mixed
    from avenir_tpu.ops.distance import blocked_topk_neighbors

    bins = (4, 3)
    x_num = rng.normal(size=(2000, 3)).astype(np.float32) * 5
    ranges = np.full(3, 10.0, np.float32)
    x_cat = np.stack([rng.integers(0, b, 2000) for b in bins], 1).astype(
        np.int32)
    q_num, q_cat = x_num[:256], x_cat[:256]
    for metric in ("euclidean", "manhattan"):
        # the reference at highest precision: a TPU's default matmul
        # passes are bf16, ~4e-3 relative on these distances
        with jax.default_matmul_precision("highest"):
            ref_d, _ = blocked_topk_neighbors(
                jnp.asarray(q_num), jnp.asarray(x_num), jnp.asarray(q_cat),
                jnp.asarray(x_cat), cat_bins=bins,
                num_ranges=jnp.asarray(ranges), k=4, block=2000,
                metric=metric)
        xe, n_attrs = _expand_mixed(x_num, ranges, x_cat, bins, metric)
        qe, _ = _expand_mixed(q_num, ranges, q_cat, bins, metric)
        t_pad, _, n_valid = pad_train(xe, None, 512)
        got_d, _ = knn_topk_lanes(
            jnp.asarray(np.ascontiguousarray(qe)), jnp.asarray(t_pad), k=4,
            block_q=256, block_t=512, metric=metric, n_valid=n_valid,
            n_attrs=n_attrs, **kw)
        # the queries are train rows, so each nearest distance is exactly
        # 0, where sqrt turns the ~1e-6 rounding of qs + ts - 2 q.t into
        # ~1e-3: compare euclidean distances squared
        power = 2 if metric == "euclidean" else 1
        ok = np.allclose(np.asarray(got_d) ** power,
                         np.asarray(ref_d) ** power, rtol=3e-3 * power,
                         atol=2e-5 if power == 2 else 1e-4)
        print(f"{'PASS' if ok else 'FAIL'} mixed-onehot/{metric}")
        results.append(ok)

    # same-lane collision stress for the lane kernel, compiled
    q = np.zeros((128, 4), np.float32)
    t = rng.normal(size=(2048, 4)).astype(np.float32) * 10
    cols = [3, 131, 259, 515, 899]
    for rank, c in enumerate(cols):
        t[c] = 0.01 * (rank + 1)
    dl, il = knn_topk_lanes(jnp.asarray(q), jnp.asarray(t), k=5,
                            block_q=128, block_t=256, **kw)
    ok = set(np.asarray(il)[0].tolist()) == set(cols)
    print(f"{'PASS' if ok else 'FAIL'} lanes/same-lane-collision")
    results.append(ok)

    results.append(exact_at_cells_shape(kw, quick))

    return int(sum(results)), len(results)


def main():
    from avenir_tpu.utils.devices import require_backend

    platform = require_backend()
    if platform != "tpu":
        sys.exit(f"tpu_kernel_check compiles for a TPU; this process runs "
                 f"on {platform!r}")
    passed, total = run_cases()
    print(json.dumps({"metric": "tpu_kernel_check", "passed": passed,
                      "total": total}))
    return 0 if passed == total else 1


if __name__ == "__main__":
    sys.exit(main())
