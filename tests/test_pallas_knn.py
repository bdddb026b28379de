"""Pallas fused distance+top-k kernel vs the jnp reference path.

Runs in pallas interpret mode on the CPU test mesh; the compiled path is
exercised on real TPU by tools/tpu_kernel_check.py, chip_smoke.py and
the benchmark's kNN cells."""

import numpy as np
import pytest
import jax.numpy as jnp

from avenir_tpu.ops.distance import blocked_topk_neighbors, pad_train
from avenir_tpu.ops.pallas_knn import knn_topk_lanes, knn_topk_pallas


@pytest.mark.parametrize("metric", ["euclidean", "manhattan"])
def test_kernel_matches_jnp_path(metric):
    rng = np.random.default_rng(0)
    nq, nt, d, k = 256, 512, 8, 5
    q = rng.normal(size=(nq, d)).astype(np.float32)
    t = rng.normal(size=(nt, d)).astype(np.float32)

    ref_d, ref_i = blocked_topk_neighbors(
        jnp.asarray(q), jnp.asarray(t), k=k, block=nt, metric=metric)
    got_d, got_i, _ = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t), k=k, block_q=128, block_t=256,
        metric=metric, interpret=True)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref_d),
                               rtol=1e-4, atol=1e-5)
    # indices may differ on exact distance ties; check distance-equivalence
    same = np.asarray(got_i) == np.asarray(ref_i)
    if not same.all():
        gd, rd = np.asarray(got_d), np.asarray(ref_d)
        np.testing.assert_allclose(gd[~same], rd[~same], rtol=1e-4)


def test_kernel_masks_padding():
    rng = np.random.default_rng(1)
    nq, d, k = 128, 4, 3
    q = rng.normal(size=(nq, d)).astype(np.float32)
    t_real = rng.normal(size=(100, d)).astype(np.float32)
    t_pad, _, n_valid = pad_train(t_real, None, 128)
    got_d, got_i, _ = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=128, block_t=128,
        n_valid=n_valid, interpret=True)
    assert (np.asarray(got_i) < 100).all()
    assert (np.asarray(got_i) >= 0).all()
    assert np.isfinite(np.asarray(got_d)).all()


def test_kernel_multi_block_merge():
    """Best neighbors scattered across train blocks must all surface."""
    rng = np.random.default_rng(2)
    nq, d, k = 128, 4, 4
    q = np.zeros((nq, d), np.float32)
    t = rng.normal(size=(512, d)).astype(np.float32) * 10
    # plant the 4 nearest rows in 4 different 128-blocks
    for b, scale in enumerate([0.01, 0.02, 0.03, 0.04]):
        t[b * 128 + 7] = scale
    got_d, got_i, _ = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t), k=k, block_q=128, block_t=128,
        interpret=True)
    expect = {7, 135, 263, 391}
    assert set(np.asarray(got_i)[0].tolist()) == expect
    # ascending order
    assert (np.diff(np.asarray(got_d), axis=1) >= -1e-7).all()


def test_kernel_small_train_fills_with_sentinels():
    q = np.zeros((128, 2), np.float32)
    t_real = np.ones((2, 2), np.float32)
    t_pad, _, n_valid = pad_train(t_real, None, 128)
    got_d, got_i, _ = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t_pad), k=4, block_q=128, block_t=128,
        n_valid=n_valid, interpret=True)
    d0, i0 = np.asarray(got_d)[0], np.asarray(got_i)[0]
    assert np.isfinite(d0[:2]).all() and set(i0[:2]) == {0, 1}
    assert np.isinf(d0[2:]).all() and (i0[2:] == -1).all()


@pytest.mark.parametrize("case", ["basic", "pad", "tiny", "multiblock"])
def test_packed_kernel_matches_oracle(case):
    """Packed-key insertion-network path: quantized to ~2^-12 relative but
    must find the same neighbor sets as the exact oracle."""
    rng = np.random.default_rng(3)
    nq, d, k = 128, 8, 5
    q = rng.normal(size=(nq, d)).astype(np.float32)
    if case == "tiny":
        t = rng.normal(size=(3, d)).astype(np.float32)
    elif case == "multiblock":
        t = rng.normal(size=(1024, d)).astype(np.float32)
    else:
        t = rng.normal(size=(300 if case == "pad" else 512, d)).astype(
            np.float32)
    t_pad, _, n_valid = pad_train(t, None, 256)

    got_d, got_i, _ = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=128, block_t=256,
        n_valid=n_valid, interpret=True, packed=True)
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)

    full = np.sqrt(((q[:, None, :] - t[None, :, :]) ** 2).mean(-1))
    order = np.argsort(full, axis=1)[:, :k]
    kk = min(k, t.shape[0])
    ref_d = np.take_along_axis(full, order, axis=1)

    np.testing.assert_allclose(got_d[:, :kk], ref_d[:, :kk],
                               rtol=3e-4, atol=1e-5)
    # neighbor-set recall (ties within quantization may reorder)
    recall = np.mean([
        len(set(got_i[r, :kk]) & set(order[r, :kk])) / kk for r in range(nq)
    ])
    assert recall >= 0.99
    if kk < k:  # unfillable slots
        assert np.isinf(got_d[:, kk:]).all()
        assert (got_i[:, kk:] == -1).all()
    # ascending within the filled slots (diff of two infs is NaN)
    assert (np.diff(got_d[:, :kk], axis=1) >= -1e-7).all()


@pytest.mark.parametrize("case", ["basic", "pad", "tiny", "multiblock"])
def test_lane_kernel_matches_oracle(case):
    """Lane-resident packed kernel (global chunk ids, deferred extraction):
    quantized to 2^-(23-pack_bits) relative but must find the same neighbor
    sets as the exact oracle, across train-block boundaries."""
    rng = np.random.default_rng(4)
    nq, d, k = 128, 8, 5
    q = rng.normal(size=(nq, d)).astype(np.float32)
    if case == "tiny":
        t = rng.normal(size=(3, d)).astype(np.float32)
    elif case == "multiblock":
        t = rng.normal(size=(1024, d)).astype(np.float32)
    else:
        t = rng.normal(size=(300 if case == "pad" else 512, d)).astype(
            np.float32)
    t_pad, _, n_valid = pad_train(t, None, 256)

    got_d, got_i = knn_topk_lanes(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=128, block_t=256,
        n_valid=n_valid, interpret=True)
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)

    full = np.sqrt(((q[:, None, :] - t[None, :, :]) ** 2).mean(-1))
    order = np.argsort(full, axis=1)[:, :k]
    kk = min(k, t.shape[0])
    ref_d = np.take_along_axis(full, order, axis=1)

    np.testing.assert_allclose(got_d[:, :kk], ref_d[:, :kk],
                               rtol=3e-4, atol=1e-5)
    recall = np.mean([
        len(set(got_i[r, :kk]) & set(order[r, :kk])) / kk for r in range(nq)
    ])
    assert recall >= 0.99
    if kk < k:
        assert np.isinf(got_d[:, kk:]).all()
        assert (got_i[:, kk:] == -1).all()
    assert (np.diff(got_d[:, :kk], axis=1) >= -1e-7).all()


def test_lane_kernel_same_lane_collisions():
    """Up to k nearest neighbors planted in ONE lane (columns congruent
    mod 128) must all survive the per-lane k-deep carry."""
    rng = np.random.default_rng(5)
    nq, d, k = 128, 4, 5
    q = np.zeros((nq, d), np.float32)
    t = rng.normal(size=(1024, d)).astype(np.float32) * 10
    # plant the 5 nearest rows all in lane 3: columns 3, 131, 259, 515, 899
    cols = [3, 131, 259, 515, 899]
    for rank, c in enumerate(cols):
        t[c] = 0.01 * (rank + 1)
    got_d, got_i = knn_topk_lanes(
        jnp.asarray(q), jnp.asarray(t), k=k, block_q=128, block_t=256,
        interpret=True)
    assert set(np.asarray(got_i)[0].tolist()) == set(cols)
    assert (np.diff(np.asarray(got_d), axis=1) >= -1e-7).all()


def test_lane_kernel_rejects_oversize_corpus():
    q = np.zeros((128, 2), np.float32)
    t = np.zeros((128 * 4096 + 256, 2), np.float32)
    with pytest.raises(AssertionError, match="chunk-id bits"):
        knn_topk_lanes(jnp.asarray(q), jnp.asarray(t), k=2, block_q=128,
                       block_t=256, interpret=True)


def test_packed_kernel_rejects_oversize_block():
    q = np.zeros((128, 2), np.float32)
    t = np.zeros((8192, 2), np.float32)
    with pytest.raises(AssertionError, match="packed"):
        knn_topk_pallas(jnp.asarray(q), jnp.asarray(t), k=2, block_q=128,
                        block_t=8192, interpret=True, packed=True)


@pytest.mark.parametrize("kernel_fn,metric", [
    ("none", "euclidean"), ("gaussian", "euclidean"),
    ("linearAdditive", "manhattan"), ("linearMultiplicative", "euclidean"),
])
def test_fused_classify_matches_composed_vote(kernel_fn, metric):
    """knn_classify_lanes (in-kernel vote, label-packed keys) must produce
    the composed top-k + _vote class scores: same kernel formulas, same
    padding semantics; distance quantization is 2^-21ish so scores match
    to the floor-boundary tolerance."""
    from avenir_tpu.models.knn import _vote
    from avenir_tpu.ops.pallas_knn import knn_classify_lanes

    rng = np.random.default_rng(9)
    nq, d, k, C = 128, 6, 5, 3
    q = rng.normal(size=(nq, d)).astype(np.float32)
    t = rng.normal(size=(700, d)).astype(np.float32)
    labels = rng.integers(0, C, 700).astype(np.int32)
    t_pad, _, n_valid = pad_train(t, None, 256)
    lab_pad = np.zeros(t_pad.shape[0], np.int32)
    lab_pad[:700] = labels

    scores = np.asarray(knn_classify_lanes(
        jnp.asarray(q), jnp.asarray(t_pad), jnp.asarray(lab_pad), k=k,
        n_classes=C, kernel_fn=kernel_fn, kernel_param=30.0, block_q=128,
        block_t=256, metric=metric, n_valid=n_valid, interpret=True))

    dist, idx = knn_topk_lanes(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=128, block_t=256,
        metric=metric, n_valid=n_valid, interpret=True)
    ref = np.asarray(_vote(dist, jnp.asarray(lab_pad)[jnp.maximum(idx, 0)],
                           jnp.ones_like(dist), kernel_fn, 30.0, C,
                           False, False))
    # the two paths quantize distances differently (label bits vs chunk-id
    # bits); floor(d*100) can differ by one step on boundary-sitting
    # distances, moving one neighbor's score between classes
    assert np.abs(scores - ref).max() <= 2.0 or np.allclose(scores, ref)
    agree = (scores.argmax(1) == ref.argmax(1)).mean()
    assert agree >= 0.99, f"fused vs composed argmax agreement {agree}"


def test_fused_classify_unfilled_slots_and_small_corpus():
    from avenir_tpu.ops.pallas_knn import knn_classify_lanes

    rng = np.random.default_rng(10)
    q = rng.normal(size=(128, 4)).astype(np.float32)
    t = rng.normal(size=(3, 4)).astype(np.float32)
    labels = np.array([0, 1, 1], np.int32)
    t_pad, _, n_valid = pad_train(t, None, 256)
    lab_pad = np.zeros(256, np.int32)
    lab_pad[:3] = labels
    scores = np.asarray(knn_classify_lanes(
        jnp.asarray(q), jnp.asarray(t_pad), jnp.asarray(lab_pad), k=5,
        n_classes=2, kernel_fn="none", block_q=128, block_t=256,
        n_valid=n_valid, interpret=True))
    # only 3 real neighbors exist: every query's total vote mass is 3
    np.testing.assert_allclose(scores.sum(axis=1), 3.0)
    np.testing.assert_allclose(scores[:, 0], 1.0)


def test_fused_classify_exhausted_rounds_stay_finite():
    """Regression: when the candidate buffer runs dry before k rounds
    (tiny corpus), later rounds read the int32-max fill value, whose
    label-masked bits BITCAST to NaN; with a real kernel function the
    epilogue must select 0, not multiply the NaN by a zero take."""
    from avenir_tpu.ops.pallas_knn import knn_classify_lanes

    rng = np.random.default_rng(12)
    q = rng.normal(size=(128, 4)).astype(np.float32)
    t = rng.normal(size=(3, 4)).astype(np.float32)
    labels = np.array([0, 1, 1], np.int32)
    t_pad, _, n_valid = pad_train(t, None, 256)
    lab_pad = np.zeros(256, np.int32)
    lab_pad[:3] = labels
    for kernel_fn in ("gaussian", "linearAdditive", "linearMultiplicative"):
        scores = np.asarray(knn_classify_lanes(
            jnp.asarray(q), jnp.asarray(t_pad), jnp.asarray(lab_pad), k=5,
            n_classes=2, kernel_fn=kernel_fn, kernel_param=30.0,
            block_q=128, block_t=256, n_valid=n_valid, interpret=True))
        assert np.isfinite(scores).all(), kernel_fn


def test_mixed_expansion_matches_jnp_mixed_distance():
    """One-hot-expanded mixed data through the numeric kernel must equal
    ops.distance's mixed pairwise semantics (the route churn-shaped data
    takes on TPU now)."""
    from avenir_tpu.models.knn import _expand_mixed
    from avenir_tpu.ops.distance import blocked_topk_neighbors

    rng = np.random.default_rng(11)
    n, dn, dc = 300, 3, 2
    bins = (4, 3)
    x_num = rng.normal(size=(n, dn)).astype(np.float32) * 5
    ranges = np.array([10.0, 10.0, 10.0], np.float32)
    x_cat = np.stack([rng.integers(0, b, n) for b in bins], 1).astype(np.int32)
    q_num, q_cat = x_num[:64], x_cat[:64]

    for metric in ("euclidean", "manhattan"):
        ref_d, ref_i = blocked_topk_neighbors(
            jnp.asarray(q_num), jnp.asarray(x_num), jnp.asarray(q_cat),
            jnp.asarray(x_cat), cat_bins=bins,
            num_ranges=jnp.asarray(ranges), k=4, block=100, metric=metric)

        xe, n_attrs = _expand_mixed(x_num, ranges, x_cat, bins, metric)
        qe, _ = _expand_mixed(q_num, ranges, q_cat, bins, metric)
        assert n_attrs == dn + dc
        t_pad, _, n_valid = pad_train(xe, None, 256)
        got_d, got_i = knn_topk_lanes(
            jnp.asarray(np.ascontiguousarray(qe[:64])), jnp.asarray(t_pad),
            k=4, block_q=64, block_t=256, metric=metric, n_valid=n_valid,
            n_attrs=n_attrs, interpret=True)
        # atol floor: the packed kernel quantizes distances to
        # 2^-(23-_PACK_BITS)=2^-11 relative (pallas_knn docstring), which
        # at these O(0.25) magnitudes is ~1.2e-4 per distance — 1e-4 was
        # asserting below the kernel's own documented precision
        np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref_d),
                                   rtol=3e-3, atol=5e-4)


def test_randomized_shape_sweep_vs_oracle():
    """Randomized interpret-mode sweep over (nq, nt, k, n_valid, metric):
    the lane kernel top-k must match a NumPy oracle for every
    drawn configuration (tie-tolerant on indices). Catches shape-dependent
    carry/padding bugs the fixed-shape tests can't."""
    rng = np.random.default_rng(77)
    for trial in range(8):
        k = int(rng.integers(1, 9))
        d = int(rng.choice([4, 8, 16]))
        nq = 128 * int(rng.integers(1, 3))
        # low end of 2 lets n_real fall BELOW k: the unfillable-slot
        # (inf / -1 sentinel) path must be drawable, not dead
        n_real = int(rng.integers(2, 700))
        metric = str(rng.choice(["euclidean", "manhattan"]))
        block_t = 256
        q = rng.normal(size=(nq, d)).astype(np.float32)
        t = rng.normal(size=(n_real, d)).astype(np.float32)
        t_pad, _, n_valid = pad_train(t, None, block_t)
        got_d, got_i = knn_topk_lanes(
            jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=128,
            block_t=block_t, n_valid=n_valid, metric=metric,
            interpret=True)
        got_d, got_i = np.asarray(got_d), np.asarray(got_i)

        if metric == "euclidean":
            full = np.sqrt(((q[:, None, :] - t[None, :, :]) ** 2).mean(-1))
        else:
            full = np.abs(q[:, None, :] - t[None, :, :]).sum(-1) / d
        kk = min(k, n_real)
        ref_d = np.sort(full, axis=1)[:, :kk]
        np.testing.assert_allclose(
            got_d[:, :kk], ref_d, rtol=3e-3, atol=1e-4,
            err_msg=f"trial {trial}: k={k} d={d} nq={nq} n_real={n_real}")
        # returned indices must point at rows whose true distance matches
        rows = np.arange(nq, dtype=np.int32)[:, None]
        np.testing.assert_allclose(
            full[rows, got_i[:, :kk]], got_d[:, :kk], rtol=3e-3, atol=1e-4)
        if kk < k:
            assert np.isinf(got_d[:, kk:]).all()
            assert (got_i[:, kk:] == -1).all()


def test_randomized_classify_sweep_fused_vs_composed():
    """Randomized fused-vote configurations (k, classes, kernel_fn,
    corpus size) against the composed top-k + _vote path."""
    from avenir_tpu.models.knn import _vote
    from avenir_tpu.ops.pallas_knn import knn_classify_lanes

    rng = np.random.default_rng(88)
    for trial in range(4):
        k = int(rng.integers(1, 8))
        C = int(rng.integers(2, 5))
        kernel_fn = str(rng.choice(["none", "gaussian", "linearAdditive"]))
        n_real = int(rng.integers(max(k, 3), 600))
        d = 6
        q = rng.normal(size=(128, d)).astype(np.float32)
        t = rng.normal(size=(n_real, d)).astype(np.float32)
        labels = rng.integers(0, C, n_real).astype(np.int32)
        t_pad, _, n_valid = pad_train(t, None, 256)
        lab_pad = np.zeros(t_pad.shape[0], np.int32)
        lab_pad[:n_real] = labels

        scores = np.asarray(knn_classify_lanes(
            jnp.asarray(q), jnp.asarray(t_pad), jnp.asarray(lab_pad), k=k,
            n_classes=C, kernel_fn=kernel_fn, kernel_param=30.0,
            block_q=128, block_t=256, n_valid=n_valid, interpret=True))
        assert np.isfinite(scores).all(), (trial, kernel_fn)

        dist, idx = knn_topk_lanes(
            jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=128,
            block_t=256, n_valid=n_valid, interpret=True)
        ref = np.asarray(_vote(dist, jnp.asarray(lab_pad)[jnp.maximum(idx, 0)],
                               jnp.ones_like(dist), kernel_fn, 30.0, C,
                               False, False))
        agree = (scores.argmax(1) == ref.argmax(1)).mean()
        assert agree >= 0.98, (trial, kernel_fn, agree)


# ------------------------------------------- the exact kernel, bit for bit
def _k_least_pairs(q, t, k, metric):
    """The k least (distance, index) pairs a query in lexicographic order,
    plain numpy: the features added in the kernel's order in float32 (the
    euclidean cases are whole numbers, exact in any order), a stable sort
    on the distance; (+inf, -1) where the corpus runs out."""
    acc = np.zeros((q.shape[0], t.shape[0]), np.float32)
    for f in range(q.shape[1]):
        diff = q[:, f][:, None] - t[:, f][None, :]
        acc = acc + (np.abs(diff) if metric == "manhattan" else diff * diff)
    if metric == "euclidean":
        acc = np.sqrt(acc)
    idx = np.argsort(acc, axis=1, kind="stable")[:, :k]
    dist = np.take_along_axis(acc, idx, axis=1)
    short = k - idx.shape[1]
    if short > 0:
        dist = np.pad(dist, ((0, 0), (0, short)), constant_values=np.inf)
        idx = np.pad(idx, ((0, 0), (0, short)), constant_values=-1)
    return dist, idx.astype(np.int32)


def _sorted_by_distance(t, q0, metric, falling):
    """`t` in rising (or falling) order of its rows' distance to `q0`, rows
    at the same distance dropped: every row strictly nearer (or farther)
    than the one before it."""
    diff = t - q0[None, :]
    dist = np.abs(diff).sum(1) if metric == "manhattan" else (diff ** 2).sum(1)
    _, first = np.unique(dist, return_index=True)
    t = t[first]
    return t[::-1].copy() if falling else t


#: (id, query rows, train rows, block_q, block_t, k, values, order)
_EXACT_CASES = [
    ("ties", 256, 768, 128, 256, 5, "few", "drawn"),
    ("ties-one-tile", 128, 2048, 128, 2048, 5, "few", "drawn"),
    ("falling", 256, 3000, 128, 256, 5, "whole", "falling"),
    ("rising", 256, 3000, 128, 256, 5, "whole", "rising"),
    ("falling-8192", 128, 20000, 128, 8192, 5, "whole", "falling"),
    ("rising-8192", 128, 20000, 128, 8192, 5, "whole", "rising"),
    ("block-256", 128, 1024, 128, 256, 5, "whole", "drawn"),
    ("block-768", 128, 2304, 128, 768, 5, "whole", "drawn"),
    ("block-2048", 128, 6144, 128, 2048, 5, "whole", "drawn"),
    ("block-8192", 128, 16384, 128, 8192, 5, "whole", "drawn"),
    ("padded-tail", 128, 1500, 128, 768, 5, "whole", "drawn"),
    ("padded-tail-8192", 128, 9000, 128, 8192, 3, "whole", "drawn"),
    ("smaller-than-k", 128, 3, 128, 256, 5, "whole", "drawn"),
    ("tiles-and-blocks", 512, 4096, 128, 512, 5, "whole", "drawn"),
    ("k1", 256, 2048, 256, 1024, 1, "few", "drawn"),
    ("k8", 128, 2048, 128, 512, 8, "whole", "drawn"),
]


def _exact_case(case, metric):
    name, nq, nt, bq, bt, k, values, order = case
    rng = np.random.default_rng([37, len(name), nt])
    d = 9
    hi = np.array([600, 200, 100, 28, 100, 100, 280, 180, 26], np.float32)
    if values == "few":
        q = rng.integers(0, 4, (nq, d)).astype(np.float32)
        t = rng.integers(0, 4, (nt, d)).astype(np.float32)
    else:
        q = np.floor(rng.random((nq, d)) * (hi + 1)).astype(np.float32)
        t = np.floor(rng.random((nt, d)) * (hi + 1)).astype(np.float32)
        if metric == "manhattan":       # shares of the ranges: real sums
            q, t = q / hi, t / hi
    if order != "drawn":
        q = np.broadcast_to(q[:1], q.shape).copy()
        t = _sorted_by_distance(t, q[0], metric, falling=order == "falling")
    return q, t, bq, bt, k


@pytest.mark.parametrize("metric", ["manhattan", "euclidean"])
@pytest.mark.parametrize("case", _EXACT_CASES, ids=[c[0] for c in _EXACT_CASES])
def test_exact_kernel_is_the_k_least_pairs_bit_for_bit(case, metric):
    """ISSUE 37: a slice is extracted only where a row of it lies strictly
    under its query's k-th best, and the result is the same bits as the
    whole-tile extraction's: the k least (distance, index) pairs."""
    from avenir_tpu.ops.pallas_knn import slice_rows

    q, t, bq, bt, k = _exact_case(case, metric)
    t_pad, _, n_valid = pad_train(t, None, bt)
    got_d, got_i, ext = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=bq, block_t=bt,
        metric=metric, n_valid=n_valid, n_attrs=1, interpret=True)
    want_d, want_i = _k_least_pairs(q, t, k, metric)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_array_equal(np.asarray(got_d), want_d)

    ext = np.asarray(ext)
    slices = t_pad.shape[0] // slice_rows(bt)       # a query block's
    assert ext.shape == (q.shape[0] // bq,)
    assert (ext >= 1).all() and (ext <= slices).all()
    order = case[-1]
    if order == "falling":          # every slice holds a nearer row
        real = -(-n_valid // slice_rows(bt))
        assert (ext == real).all(), (ext, real)
    if order == "rising":           # only the head does
        assert (ext == 1).all(), ext


def test_exact_kernel_with_rows_too_wide_to_keep_their_broadcasts():
    """Above _QUERY_BROADCAST_BYTES the query columns' lane broadcasts are
    made for every slice, not once a query block: the same answer."""
    from avenir_tpu.ops.pallas_knn import _hoists_queries

    d, bq, bt, k = 80, 128, 256, 4
    assert _hoists_queries(9, 256, 8192) and not _hoists_queries(d, bq, bt)
    rng = np.random.default_rng(80)
    q = (rng.integers(0, 7, (bq, d)) / 7).astype(np.float32)
    t = (rng.integers(0, 7, (700, d)) / 7).astype(np.float32)
    t_pad, _, n_valid = pad_train(t, None, bt)
    got_d, got_i, _ = knn_topk_pallas(
        jnp.asarray(q), jnp.asarray(t_pad), k=k, block_q=bq, block_t=bt,
        metric="manhattan", n_valid=n_valid, n_attrs=1, interpret=True)
    want_d, want_i = _k_least_pairs(q, t, k, "manhattan")
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_array_equal(np.asarray(got_d), want_d)


@pytest.mark.parametrize("block_t,want", [
    (128, 128), (256, 256), (512, 512), (768, 256), (1024, 1024),
    (2048, 2048), (2304, 256), (8192, 2048), (100, 100), (64, 64)])
def test_slice_rows_divides_every_block(block_t, want):
    from avenir_tpu.ops.pallas_knn import slice_rows

    assert slice_rows(block_t) == want and block_t % slice_rows(block_t) == 0
