"""KNN vs NumPy oracle: neighbor sets, kernel votes, regression modes."""

import numpy as np
import pytest

from avenir_tpu.data import generate_elearn, generate_churn
from avenir_tpu.models.knn import (
    KERNEL_SCALE,
    NearestNeighborClassifier,
    NearestNeighborRegressor,
)


@pytest.fixture(scope="module")
def elearn_train():
    return generate_elearn(800, seed=1)


@pytest.fixture(scope="module")
def elearn_test():
    return generate_elearn(100, seed=2)


def _oracle_knn(train, test, k):
    """Manhattan avg-per-attribute distance + top-k (numpy)."""
    xt = train.feature_matrix()
    xq = test.feature_matrix()
    rng = np.array([100.0] * xt.shape[1], dtype=np.float32)
    d = np.abs(xq[:, None, :] / rng - xt[None, :, :] / rng).sum(-1) / xt.shape[1]
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


class TestClassification:
    def test_neighbor_sets_match_oracle(self, elearn_train, elearn_test):
        clf = NearestNeighborClassifier(elearn_train, top_match_count=5, block=128)
        dist, idx = clf.neighbors(elearn_test)
        od, oidx = _oracle_knn(elearn_train, elearn_test, 5)
        np.testing.assert_allclose(np.sort(dist, 1), od, atol=1e-5)
        for r in range(len(elearn_test)):
            assert set(np.asarray(idx[r])) == set(oidx[r])

    def test_majority_vote_accuracy(self, elearn_train, elearn_test):
        clf = NearestNeighborClassifier(elearn_train, top_match_count=5, block=128)
        cm = clf.validate(elearn_test)
        assert cm.accuracy() > 0.9  # well-separated clusters

    @pytest.mark.parametrize(
        "kernel", ["none", "linearMultiplicative", "linearAdditive", "gaussian"]
    )
    def test_kernels_match_reference_formulas(self, kernel, elearn_train, elearn_test):
        clf = NearestNeighborClassifier(
            elearn_train, top_match_count=5, kernel_function=kernel,
            kernel_param=30.0, block=128,
        )
        dist, idx = clf.neighbors(elearn_test)
        y = np.asarray(clf.train_labels)[np.asarray(idx)]
        d = np.floor(np.asarray(dist) * KERNEL_SCALE)
        if kernel == "none":
            s = np.ones_like(d)
        elif kernel == "linearMultiplicative":
            s = np.where(d == 0, 200.0, np.floor(KERNEL_SCALE / np.maximum(d, 1)))
        elif kernel == "linearAdditive":
            s = KERNEL_SCALE - d
        else:
            s = np.floor(KERNEL_SCALE * np.exp(-0.5 * (d / 30.0) ** 2))
        expect = np.zeros((len(elearn_test), 2))
        for q in range(len(elearn_test)):
            for j in range(5):
                expect[q, y[q, j]] += s[q, j]
        _, scores = clf.predict(elearn_test)
        np.testing.assert_allclose(scores, expect, rtol=1e-5)

    def test_mixed_categorical_numeric(self):
        train = generate_churn(400, seed=8)
        test = generate_churn(80, seed=9)
        clf = NearestNeighborClassifier(train, top_match_count=7, block=64)
        cm = clf.validate(test, pos_class=1)
        assert cm.accuracy() > 0.7

    def test_class_cond_weighting_runs(self, elearn_train, elearn_test):
        train = generate_churn(400, seed=8)
        test = generate_churn(80, seed=9)
        clf = NearestNeighborClassifier(
            train, top_match_count=7, class_cond_weighted=True, block=64
        )
        pred, scores = clf.predict(test)
        assert scores.shape == (80, 2) and (scores >= 0).all()

    def test_decision_threshold(self):
        train = generate_churn(400, seed=8)
        test = generate_churn(80, seed=9)
        lo = NearestNeighborClassifier(
            train, top_match_count=7, decision_threshold=0.1,
            positive_class="closed", block=64,
        ).predict(test)[0]
        hi = NearestNeighborClassifier(
            train, top_match_count=7, decision_threshold=10.0,
            positive_class="closed", block=64,
        ).predict(test)[0]
        # low threshold -> more positives than high threshold
        assert (lo == 1).sum() > (hi == 1).sum()


def test_weighted_scores_match_a_float64_vote_from_one_copy_of_the_rows(monkeypatch):
    """The class-conditional weights are made on the device from the rows
    the NB fold was given: the scores are those of a vote reckoned here in
    float64 over the classifier's own neighbours, and the train matrix is
    stacked once for the index and once for fold and posterior together."""
    from avenir_tpu.models.naive_bayes import NaiveBayesModel, NaiveBayesPredictor
    from tests.test_naive_bayes import feature_prob_f64, log_calls

    train = generate_elearn(20_000, seed=21)
    test = generate_elearn(200, seed=22)
    want_post = feature_prob_f64(
        NaiveBayesPredictor(NaiveBayesModel.fit(train)), train)
    stacked = log_calls(monkeypatch, "feature_matrix")
    clf = NearestNeighborClassifier(
        train, top_match_count=5, kernel_function="gaussian", kernel_param=30.0,
        class_cond_weighted=True, block=1024)
    assert stacked == ["feature_matrix"] * 2
    assert clf.train_post.shape == clf.train_labels.shape == (clf.index.n_padded,)
    assert clf.index.n_padded > len(train)
    np.testing.assert_array_equal(np.asarray(clf.train_post[len(train):]), 1.0)
    np.testing.assert_allclose(np.asarray(clf.train_post[:len(train)]),
                               want_post, rtol=2e-5)
    _, scores = clf.predict(test)
    dist, idx = (np.asarray(a) for a in clf.neighbors(test))
    t = np.floor(dist.astype(np.float64) * KERNEL_SCALE) / 30.0
    score = np.floor(KERNEL_SCALE * np.exp(-0.5 * t * t)) * want_post[idx]
    want = np.stack([(score * (train.labels()[idx] == c)).sum(axis=1)
                     for c in range(2)], axis=1)
    assert (want.sum(axis=1) > 0).all()
    np.testing.assert_allclose(scores, want, rtol=1e-4, atol=0)


class TestRegression:
    def test_average_and_median(self, elearn_train, elearn_test):
        target = elearn_train.feature_matrix()[:, 0] * 2.0
        reg = NearestNeighborRegressor(
            elearn_train, target, top_match_count=5, method="average", block=128
        )
        pred = reg.predict(elearn_test)
        # neighbors are nearby in feature space -> prediction tracks 2*act0
        true = elearn_test.feature_matrix()[:, 0] * 2.0
        assert np.corrcoef(pred, true)[0, 1] > 0.95

        med = NearestNeighborRegressor(
            elearn_train, target, top_match_count=5, method="median", block=128
        ).predict(elearn_test)
        assert np.corrcoef(med, true)[0, 1] > 0.95

    def test_linear_regression_mode(self, elearn_train, elearn_test):
        x_in = elearn_train.feature_matrix()[:, 0]
        target = 3.0 * x_in + 1.0          # exact linear relation
        reg = NearestNeighborRegressor(
            elearn_train, target, top_match_count=5,
            method="linearRegression", regr_input=x_in, block=128,
        )
        q = elearn_test.feature_matrix()[:, 0]
        pred = reg.predict(elearn_test, query_input=q)
        np.testing.assert_allclose(pred, 3.0 * q + 1.0, rtol=1e-3, atol=1e-2)


def test_classifier_fused_path_matches_composed(monkeypatch):
    """NearestNeighborClassifier(fused=True) end to end on the interpret
    kernels: the in-kernel vote must agree with the composed top-k +
    _vote path on real mixed churn data (argmax agreement; scores within
    the floor-boundary tolerance)."""
    import functools

    import avenir_tpu.ops.pallas_knn as pk
    from avenir_tpu.models.knn import NearestNeighborClassifier

    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    for name in ("knn_classify_lanes", "knn_topk_lanes", "knn_topk_pallas"):
        monkeypatch.setattr(pk, name,
                            functools.partial(getattr(pk, name),
                                              interpret=True))

    train = generate_churn(700, seed=31)
    test = generate_churn(150, seed=32)
    base = dict(top_match_count=5, kernel_function="gaussian",
                kernel_param=30.0, metric="euclidean")
    fused = NearestNeighborClassifier(train, fused=True, **base)
    assert fused.index.use_pallas and fused.index.n_attrs == 5
    composed = NearestNeighborClassifier(train, fused=False, **base)
    pf, sf = fused.predict(test)
    pc, sc = composed.predict(test)
    agree = (pf == pc).mean()
    assert agree >= 0.98, agree
    # churn features are heavily quantized, so equal-distance neighbor sets
    # are common and the two paths may legally pick different tied members
    # (different labels): total vote mass must match exactly, and rows
    # whose scores differ at all must be rare
    np.testing.assert_allclose(sf.sum(axis=1), sc.sum(axis=1), atol=1e-3)
    exact = (np.abs(sf - sc).max(axis=1) <= 2.0).mean()
    assert exact >= 0.95, exact


def test_classifier_fast_path_toggles(monkeypatch):
    """packed=True + fused=True through the REAL (interpret-mode) pallas
    kernels on a 300-row corpus — a size whose 128-granular padding is an
    odd multiple, which the packed path must survive (the lane kernels
    require block_t % 256 == 0) — must match the default exact path."""
    import functools

    import avenir_tpu.ops.pallas_knn as pk
    from avenir_tpu.data import generate_elearn
    from avenir_tpu.models.knn import NearestNeighborClassifier

    ds = generate_elearn(300, seed=6)
    test = generate_elearn(80, seed=7)
    base = NearestNeighborClassifier(ds, top_match_count=3,
                                     kernel_function="gaussian",
                                     kernel_param=30.0, metric="euclidean")
    bp, _ = base.predict(test)

    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    for name in ("knn_classify_lanes", "knn_topk_lanes", "knn_topk_pallas"):
        monkeypatch.setattr(pk, name,
                            functools.partial(getattr(pk, name),
                                              interpret=True))
    fast = NearestNeighborClassifier(ds, top_match_count=3,
                                     kernel_function="gaussian",
                                     kernel_param=30.0, metric="euclidean",
                                     packed=True, fused=True)
    fp, _ = fast.predict(test)
    np.testing.assert_array_equal(bp, fp)
    # packed WITHOUT fused: predict() must route through the packed
    # lane top-k (fused short-circuits neighbors(), so this is the only
    # configuration that executes knn_topk_lanes here)
    packed_only = NearestNeighborClassifier(ds, top_match_count=3,
                                            kernel_function="gaussian",
                                            kernel_param=30.0,
                                            metric="euclidean", packed=True)
    assert packed_only.index.packed
    pp, _ = packed_only.predict(test)
    np.testing.assert_array_equal(bp, pp)


def test_packed_over_corpus_cap_falls_back(monkeypatch):
    """packed=True over a corpus beyond the lane kernel's chunk-id cap
    must silently use the exact kernel instead of tripping its assert."""
    import functools

    import avenir_tpu.ops.pallas_knn as pk
    from avenir_tpu.data import generate_elearn
    from avenir_tpu.models.knn import NeighborIndex

    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    monkeypatch.setattr(pk, "LANE_CORPUS_CAP", 256)      # tiny cap for test
    monkeypatch.setattr(pk, "knn_topk_pallas",
                        functools.partial(pk.knn_topk_pallas,
                                          interpret=True))
    def _boom(*a, **k):
        raise AssertionError("lane kernel must not be called over the cap")
    monkeypatch.setattr(pk, "knn_topk_lanes", _boom)

    idx = NeighborIndex(generate_elearn(600, seed=9), k=3,
                        metric="euclidean", packed=True)
    d, i = idx.neighbors(generate_elearn(64, seed=10))
    import numpy as np
    assert np.isfinite(np.asarray(d)).all()
