"""Naive Bayes vs an independent NumPy oracle + model-file round trip."""

import numpy as np
import pytest

from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.data import generate_churn, churn_schema
from avenir_tpu.models.naive_bayes import NaiveBayesModel, NaiveBayesPredictor
from avenir_tpu.utils.metrics import CostBasedArbitrator


@pytest.fixture(scope="module")
def churn():
    return generate_churn(2000, seed=3)


@pytest.fixture(scope="module")
def model(churn):
    return NaiveBayesModel.fit(churn)


def _oracle_posteriors(ds):
    """Independent NumPy NB: P(C|F) = prod_f P(bin_f|C) * P(C) / prod_f P(bin_f)."""
    codes, bins = ds.feature_codes()
    y = ds.labels()
    n, F = codes.shape
    K = ds.schema.num_classes()
    post = []
    prior = []
    for f in range(F):
        pf = np.zeros((K, bins[f]), np.float64)
        for k in range(K):
            pf[k] = np.bincount(codes[y == k, f], minlength=bins[f])
        post.append(pf / np.maximum(pf.sum(1, keepdims=True), 1e-30))
        tot = pf.sum(0)
        prior.append(tot / tot.sum())
    pc = np.bincount(y, minlength=K) / n
    out = np.zeros((n, K))
    for i in range(n):
        fprior = np.prod([prior[f][codes[i, f]] for f in range(F)])
        for k in range(K):
            fpost = np.prod([post[f][k, codes[i, f]] for f in range(F)])
            out[i, k] = fpost * pc[k] / max(fprior, 1e-30)
    return out


class TestTrain:
    def test_counts_match_bincount(self, churn, model):
        codes, bins = churn.feature_codes()
        y = churn.labels()
        for f in range(len(bins)):
            for k in range(2):
                expect = np.bincount(codes[y == k, f], minlength=bins[f])
                np.testing.assert_allclose(
                    model.post_counts[f, k, : bins[f]], expect
                )
        np.testing.assert_allclose(model.class_counts, np.bincount(y, minlength=2))

    def test_streaming_accumulate_equals_single_pass(self, churn, model):
        m2 = NaiveBayesModel.empty(churn.schema)
        half = len(churn) // 2
        for part in (churn.take(np.arange(half)), churn.take(np.arange(half, len(churn)))):
            codes, _ = part.feature_codes(m2.binned_fields)
            m2.accumulate(codes, part.labels(), part.feature_matrix(m2.cont_fields))
        np.testing.assert_allclose(m2.post_counts, model.post_counts)


class TestPredict:
    def test_matches_numpy_oracle(self, churn, model):
        pred, prob = NaiveBayesPredictor(model).predict(churn)
        oracle = _oracle_posteriors(churn)
        # int-percent scaling like the reference (floor(prob*100))
        oracle_pct = np.floor(np.clip(oracle, 0, None) * 100).astype(np.int32)
        np.testing.assert_array_equal(prob, oracle_pct)
        # argmax over the same int-percent space (ties break to first class,
        # as in the reference's > comparison loop)
        np.testing.assert_array_equal(pred, oracle_pct.argmax(axis=1))

    def test_learns_signal(self, churn, model):
        cm = NaiveBayesPredictor(model).validate(churn, pos_class=1)
        assert cm.accuracy() > 0.8
        counters = cm.counters()
        assert counters["Validation:Accuracy"] > 80

    def test_cost_arbitration_shifts_decisions(self, churn, model):
        arb = CostBasedArbitrator("open", "closed",
                                  false_neg_cost=10.0, false_pos_cost=1.0)
        pred_arb, _ = NaiveBayesPredictor(model, arbitrator=arb).predict(churn)
        pred_def, _ = NaiveBayesPredictor(model).predict(churn)
        # heavy positive-miss cost -> at least as many positive predictions
        assert (pred_arb == 1).sum() >= (pred_def == 1).sum()


#: the e-learning deployment's nine activity maxima (chipbench/configs)
ACTIVITY_MAX = (600, 200, 100, 28, 100, 100, 280, 180, 26)


def mixed_dataset(n, seed, binned=True, continuous=True):
    """Two classes; nine whole-number activity fields on the benchmark's
    levels (0.44 / 0.56 of each maximum, sigma 0.12 of it), a categorical
    and a bucketed int: whichever kinds are asked for, built from columns."""
    rng = np.random.default_rng(seed)
    y = (rng.random(n) < 0.5).astype(np.int32)
    fields = [{"name": "id", "ordinal": 0, "id": True, "dataType": "string"}]
    columns = {0: np.array([f"R{i}" for i in range(n)], dtype=object)}
    if continuous:
        hi = np.array(ACTIVITY_MAX, np.float64)
        x = np.clip(np.rint(rng.normal(
            np.where(y, 0.56, 0.44)[:, None], 0.12, (n, len(hi))) * hi), 0, hi)
        for j, top in enumerate(ACTIVITY_MAX):
            fields.append({"name": f"a{j}", "ordinal": len(fields),
                           "dataType": "int", "feature": True,
                           "min": 0, "max": top})
            columns[len(fields) - 1] = x[:, j].astype(np.float32)
    if binned:
        fields.append({"name": "plan", "ordinal": len(fields),
                       "dataType": "categorical", "feature": True,
                       "cardinality": ["a", "b", "c", "d", "e"]})
        columns[len(fields) - 1] = np.minimum(
            rng.integers(0, 5, n) + y * rng.integers(0, 2, n), 4).astype(np.int32)
        fields.append({"name": "age", "ordinal": len(fields), "dataType": "int",
                       "feature": True, "min": 0, "max": 120, "bucketWidth": 12})
        columns[len(fields) - 1] = np.where(
            y == 0, rng.integers(12, 120, n), rng.integers(0, 48, n)
        ).astype(np.float32)
    fields.append({"name": "status", "ordinal": len(fields),
                   "dataType": "categorical", "cardinality": ["fail", "pass"]})
    columns[len(fields) - 1] = y
    return Dataset(FeatureSchema.from_json({"fields": fields}), columns, n)


def feature_prob_f64(predictor, ds):
    """P(features | own class) of every row, reckoned plainly in float64
    numpy from the predictor's tables."""
    log_post, mean, std = (np.float64(predictor.tables[name]) for name in (
        "log_post", "cont_mean", "cont_std"))
    y = ds.labels()
    logp = np.zeros(len(ds), np.float64)
    codes, _ = ds.feature_codes(predictor.model.binned_fields)
    for f in range(codes.shape[1]):
        logp += log_post[f, y, codes[:, f]]
    x = np.float64(ds.feature_matrix(predictor.model.cont_fields))
    for f in range(x.shape[1]):
        m, s = mean[f, y], std[f, y]
        logp += -0.5 * np.log(2 * np.pi) - np.log(s) - 0.5 * ((x[:, f] - m) / s) ** 2
    return np.exp(logp)


def log_calls(monkeypatch, *names):
    """The list to which each call of `Dataset.<name>` appends its name."""
    calls = []
    for name in names:
        def logged(self, *args, _inner=getattr(Dataset, name), _name=name):
            calls.append(_name)
            return _inner(self, *args)
        monkeypatch.setattr(Dataset, name, logged)
    return calls


class TestFeatureProb:
    @pytest.mark.parametrize("kinds, n", [
        ({"binned": False}, 50_000), ({"continuous": False}, 5_000), ({}, 5_000)],
        ids=["nine_continuous", "binned_only", "both_kinds"])
    def test_device_program_against_float64_numpy(self, kinds, n):
        ds = mixed_dataset(n, seed=5, **kinds)
        pred = NaiveBayesPredictor(NaiveBayesModel.fit(ds))
        assert (len(pred.model.cont_fields), len(pred.model.binned_fields)) == (
            9 * kinds.get("continuous", True), 2 * kinds.get("binned", True))
        got = pred.feature_prob(ds)
        assert got.dtype == np.float32 and got.shape == (n,)
        want = feature_prob_f64(pred, ds)
        assert want.min() > 1e-36          # nothing near float32's floor
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=0)

    def test_fitted_and_weighted_from_one_device_copy(self, monkeypatch):
        """`fit_feature_prob` is `fit` and `feature_prob` of the same rows,
        to the bit, from one stacking of the matrix and one of the codes."""
        ds = mixed_dataset(5_000, seed=6)
        apart = NaiveBayesPredictor(NaiveBayesModel.fit(ds)).feature_prob(ds)
        calls = log_calls(monkeypatch, "feature_matrix", "feature_codes", "labels")
        pred, post = NaiveBayesPredictor.fit_feature_prob(ds)
        assert sorted(calls) == ["feature_codes", "feature_matrix", "labels"]
        np.testing.assert_array_equal(np.asarray(post), apart)
        np.testing.assert_array_equal(
            pred.model.post_counts, NaiveBayesModel.fit(ds).post_counts)

    def test_same_array_on_two_calls(self):
        ds = mixed_dataset(5_000, seed=7)
        pred = NaiveBayesPredictor(NaiveBayesModel.fit(ds))
        first = pred.feature_prob(ds)
        assert first.tobytes() == pred.feature_prob(ds).tobytes()
        assert first.tobytes() == np.asarray(pred.feature_prob_device(ds)).tobytes()

    def test_weight_under_float32_reads_zero_and_the_vote_falls_back(self):
        from avenir_tpu.models.knn import _vote

        ds = mixed_dataset(2_000, seed=8, binned=False)
        pred = NaiveBayesPredictor(NaiveBayesModel.fit(ds))
        # one row at the far corner: 3.7 sigma and more off on each of nine
        # fields, under class 0
        for j in range(9):
            ds.columns[j + 1][0] = ACTIVITY_MAX[j]
        ds.columns[10][0] = 0
        got, want = pred.feature_prob(ds), feature_prob_f64(pred, ds)
        assert 0 < want[0] < 1e-46 and got[0] == 0.0
        np.testing.assert_allclose(got[1:], want[1:], rtol=2e-5)
        # as a neighbour it votes its plain score; the others are weighted
        labels = np.array([[0, 1, 1]], np.int32)
        scores = np.asarray(_vote(
            np.zeros((1, 3), np.float32), labels, got[None, :3], "none", 1.0,
            2, True, False))
        np.testing.assert_allclose(
            scores[0], [1.0, float(got[1]) + float(got[2])], rtol=1e-6)
        assert got[1] > 0 and got[2] > 0


class TestModelFile:
    def test_csv_roundtrip(self, churn, model, tmp_path):
        p = tmp_path / "model.csv"
        model.save(str(p))
        again = NaiveBayesModel.load(str(p), churn.schema)
        pred1, prob1 = NaiveBayesPredictor(model).predict(churn)
        pred2, prob2 = NaiveBayesPredictor(again).predict(churn)
        np.testing.assert_array_equal(pred1, pred2)
        np.testing.assert_array_equal(prob1, prob2)

    def test_csv_format_rows(self, model):
        lines = model.to_csv().strip().split("\n")
        # posterior rows: classVal,ord,bin,count
        post = [l for l in lines if l.split(",")[0] != "" and l.split(",")[1] != ""]
        assert post, "no posterior rows"
        cv, o, b, c = post[0].split(",")
        assert cv in ("open", "closed") and int(o) >= 1 and int(c) > 0
        # class prior rows: classVal,,,count
        priors = [l for l in lines if l.split(",")[1] == "" and l.split(",")[0] != ""]
        assert priors and priors[0].split(",")[2] == ""


class TestSharded:
    def test_mesh_counts_equal_host(self, churn, model, mesh8):
        from avenir_tpu.parallel import shard_rows, sharded_keyed_count, row_mask
        import jax.numpy as jnp

        codes, bins = churn.feature_codes()
        y = churn.labels()
        k, bmax = 2, max(bins)

        def count(codes, labels, w):
            import jax
            oh_k = jax.nn.one_hot(labels, k, dtype=jnp.float32) * w[:, None]
            oh_b = jax.nn.one_hot(codes, bmax, dtype=jnp.float32)
            return jnp.einsum("nk,nfb->fkb", oh_k, oh_b)

        fn = sharded_keyed_count(mesh8, count)
        n = len(churn)
        cs = shard_rows(mesh8, codes)
        ys = shard_rows(mesh8, y)
        ws = row_mask(mesh8, n, cs.shape[0])
        out = np.asarray(fn(cs, ys, ws))
        np.testing.assert_allclose(out, model.post_counts, rtol=1e-5)
