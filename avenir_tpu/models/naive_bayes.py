"""Naive Bayes: class-conditional feature distributions + posterior predictor.

Reference semantics (org.avenir.bayesian):
- Train (BayesianDistribution.java): one pass over labeled CSV. Categorical /
  bucketed numeric features contribute (classVal, featureOrd, bin) -> count;
  unbinned numerics contribute (classVal, featureOrd) -> (count, sum, sum-sq)
  turned into per-class Gaussian mean/stddev (mapper :137-178, reducer
  :263-327); class priors and feature priors aggregate from the posteriors
  (cleanup :240-258). Model is a flat CSV file.
- Predict (BayesianPredictor.java): per record, per class,
  P(C|F) = P(F|C) * P(C) / P(F) with P(F|C) a product over per-feature bin
  probabilities (Gaussian density for continuous), scaled to int percent
  (:396-421); max-prob or cost-based arbitration (:342-391); confusion
  matrix counters in cleanup (:170-180).

TPU design: the two MR jobs collapse into two jitted programs. Training is
one einsum contraction onehot(class) x onehot(feature bins) -> [F, K, B]
count tensor (MXU work, no shuffle); counts are additive, so streaming
batches and mesh shards combine by psum — the same tensor algebra replaces
both the Hadoop combiner and the reducer. Prediction is a single
log-space matmul over one-hot feature codes.

Deviation from reference noted: the reference computes continuous means with
integer (long) division (BayesianDistribution.java:248); we use float math.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from avenir_tpu import obs
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureField, FeatureSchema
from avenir_tpu.utils.metrics import ConfusionMatrix, CostBasedArbitrator

_TINY = 1e-30


@dataclass
class NaiveBayesModel:
    """Count-space model (additive; finish() derives probability tables)."""

    schema: FeatureSchema
    class_values: List[str]
    binned_fields: List[FeatureField]
    cont_fields: List[FeatureField]
    bins: List[int]
    # counts: [F, K, Bmax] posterior bin counts (padded over B)
    post_counts: np.ndarray
    # continuous: [Fc, K, 3] (count, sum, sumsq) and prior [Fc, 3]
    cont_moments: np.ndarray
    class_counts: np.ndarray  # [K]
    # set when a model was loaded from CSV (mean/std known, raw moments not):
    cont_params: Optional[np.ndarray] = None        # [Fc, K, 2] (mean, std)
    cont_prior_params: Optional[np.ndarray] = None  # [Fc, 2]
    # deferred device-side accumulator (streaming ingest): a pytree of
    # per-chunk count tensors folded on device. Unweighted counts with no
    # continuous features fold as int32 (exact to 2^31 rows per cell — no
    # mid-stream flush at any realistic scale); weighted or moment-bearing
    # folds stay f32 and flush to the float64 host arrays before any cell
    # could exceed f32 integer exactness (2^24)
    _pending: Optional[tuple] = None
    _pending_rows: int = 0
    _pending_int: bool = False

    # rows a single pending f32 cell can safely absorb (2^24 ~ 16.7M,
    # with margin); crossing it flushes to host float64. int32 folds get
    # a 2^30 bound.
    _FLUSH_ROWS = 14 << 20
    _FLUSH_ROWS_INT = 1 << 30

    # ------------------------------------------------------------ training
    @classmethod
    def empty(cls, schema: FeatureSchema) -> "NaiveBayesModel":
        binned = [f for f in schema.feature_fields if f.num_bins() > 0]
        cont = [f for f in schema.feature_fields if f.is_numeric and not f.bucket_width]
        bins = [f.num_bins() for f in binned]
        k = schema.num_classes()
        bmax = max(bins) if bins else 1
        return cls(
            schema=schema,
            class_values=schema.class_values(),
            binned_fields=binned,
            cont_fields=cont,
            bins=bins,
            post_counts=np.zeros((len(binned), k, bmax), np.float64),
            cont_moments=np.zeros((len(cont), k, 3), np.float64),
            class_counts=np.zeros((k,), np.float64),
        )

    def accumulate(self, codes, labels, x_cont, weights=None,
                   defer: bool = False) -> None:
        """Add one batch of sufficient statistics.

        defer=False (default) fetches the device-computed count pytree to
        the host immediately. defer=True — the streaming-ingest path —
        folds it into a device-side accumulator instead, so a chunk loop
        dispatches asynchronously with no host round trip per chunk; the
        fold flushes to the float64 host arrays automatically before any
        cell could lose f32 integer exactness, and flush() (called by
        finish/to_csv/merge) drains the remainder."""
        k = len(self.class_values)
        bmax = self.post_counts.shape[2]
        n = labels.shape[0]
        int_mode = weights is None and self.cont_moments.shape[0] == 0
        if int_mode and jax.default_backend() == "cpu":
            # XLA:CPU pays the [n, F, bmax] one-hot einsum in memory
            # bandwidth — ~100MB of materialized one-hots per 500k-row
            # chunk for a table that is only F*K*bmax cells. Host
            # bincount builds the same integer counts directly into the
            # float64 arrays: bit-identical tables, same CPU-host
            # contract as explore._mi_chunk_counts_host.
            self.flush()
            codes_h = np.ascontiguousarray(codes, np.int32)
            y_h = np.asarray(labels, np.int32)
            yb = y_h * np.int32(bmax)
            for f in range(self.post_counts.shape[0]):
                self.post_counts[f] += np.bincount(
                    yb + codes_h[:, f],
                    minlength=k * bmax).reshape(k, bmax)
            self.class_counts += np.bincount(y_h, minlength=k)
            return
        if self._pending is not None and self._pending_int != int_mode:
            self.flush()
        w = (jnp.asarray(weights) if weights is not None
             else jnp.ones((n,), jnp.float32))
        if self._pending is None:
            f, fc = self.post_counts.shape[0], self.cont_moments.shape[0]
            dt = jnp.int32 if int_mode else jnp.float32
            self._pending = (jnp.zeros((f, k, bmax), dt),
                             jnp.zeros((fc, k, 3), jnp.float32),
                             jnp.zeros((k,), dt))
            self._pending_int = int_mode
        # count + fold is ONE jitted dispatch with a donated accumulator —
        # a chunk loop never round-trips the host (per-dispatch latency,
        # not device FLOPs, is what kills a chunked loop otherwise)
        self._pending = _fold_batch_kernel(
            self._pending, jnp.asarray(codes), jnp.asarray(labels),
            jnp.asarray(x_cont), w, k, bmax)
        # shape only — np.asarray here would fetch the whole device chunk
        self._pending_rows += int(n)
        bound = self._FLUSH_ROWS_INT if int_mode else self._FLUSH_ROWS
        if not defer or self._pending_rows >= bound:
            self.flush()

    def flush(self) -> None:
        """Drain the deferred device accumulator into the host arrays."""
        if self._pending is None:
            return
        post, mom, cls = self._pending
        self._pending = None
        self._pending_rows = 0
        self.post_counts += np.asarray(post, np.float64)
        self.cont_moments += np.asarray(mom, np.float64)
        self.class_counts += np.asarray(cls, np.float64)

    @classmethod
    def fit(cls, dataset: Dataset) -> "NaiveBayesModel":
        with obs.span("nb.fit", rows=len(dataset)):
            model = cls.empty(dataset.schema)
            codes, _ = dataset.feature_codes(model.binned_fields)
            # the matrix is a temporary of the call, so that its release
            # falls inside the span as its making does
            model.accumulate(codes, dataset.labels(),
                             dataset.feature_matrix(model.cont_fields))
        return model

    def merge(self, other: "NaiveBayesModel") -> "NaiveBayesModel":
        """Combine sufficient statistics of two partial fits (counts are
        additive — the same algebra that merges mesh shards via psum merges
        input splits; replaces the reference's reducer-side summation)."""
        if self.cont_params is not None or other.cont_params is not None:
            raise ValueError("cannot merge models loaded from CSV "
                             "(raw moments unavailable)")
        self.flush()
        other.flush()
        self.post_counts = self.post_counts + other.post_counts
        self.cont_moments = self.cont_moments + other.cont_moments
        self.class_counts = self.class_counts + other.class_counts
        return self

    # ----------------------------------------------------------- finishing
    def finish(self) -> Dict[str, jnp.ndarray]:
        """Derive the probability tables used by the jitted predictor.

        Mirrors BayesianModel.finishUp() (BayesianModel.java:217-233):
        posterior P(bin|class) normalized within class, feature prior P(bin),
        class prior P(class); continuous features get per-class and prior
        Gaussian (mean, std)."""
        self.flush()
        f, k, bmax = self.post_counts.shape
        post = self.post_counts
        post_p = post / np.maximum(post.sum(axis=2, keepdims=True), _TINY)
        prior_counts = post.sum(axis=1)                       # [F, B]
        prior_p = prior_counts / np.maximum(
            prior_counts.sum(axis=1, keepdims=True), _TINY
        )
        class_p = self.class_counts / max(self.class_counts.sum(), _TINY)

        if self.cont_params is not None:
            mean, std = self.cont_params[..., 0], self.cont_params[..., 1]
            pmean, pstd = self.cont_prior_params[..., 0], self.cont_prior_params[..., 1]
        else:
            cm = self.cont_moments
            cnt = np.maximum(cm[..., 0], _TINY)
            mean = cm[..., 1] / cnt
            var = (cm[..., 2] - cnt * mean * mean) / np.maximum(cnt - 1, 1.0)
            std = np.sqrt(np.maximum(var, _TINY))
            pm = cm.sum(axis=1)                                # prior moments [Fc,3]
            pcnt = np.maximum(pm[..., 0], _TINY)
            pmean = pm[..., 1] / pcnt
            pvar = (pm[..., 2] - pcnt * pmean * pmean) / np.maximum(pcnt - 1, 1.0)
            pstd = np.sqrt(np.maximum(pvar, _TINY))
        std = np.maximum(std, 1e-6)
        pstd = np.maximum(pstd, 1e-6)

        return {
            "log_post": jnp.asarray(np.log(np.maximum(post_p, _TINY)), jnp.float32),
            "log_prior": jnp.asarray(np.log(np.maximum(prior_p, _TINY)), jnp.float32),
            "log_class": jnp.asarray(np.log(np.maximum(class_p, _TINY)), jnp.float32),
            "cont_mean": jnp.asarray(mean, jnp.float32),
            "cont_std": jnp.asarray(std, jnp.float32),
            "cont_prior_mean": jnp.asarray(pmean, jnp.float32),
            "cont_prior_std": jnp.asarray(pstd, jnp.float32),
        }

    # ------------------------------------------------------------- file IO
    def to_csv(self, delim: str = ",") -> str:
        """Reference-compatible model CSV (BayesianDistribution reducer
        format, parsed back by BayesianPredictor.loadModel :186-224):
          classVal,ord,bin,count          feature posterior (binned)
          classVal,ord,,mean,stddev       feature posterior (continuous)
          classVal,,,count                class prior (per reduce emit)
          ,ord,bin,count                  feature prior (binned, per class)
          ,ord,,mean,stddev               feature prior (continuous)
        """
        self.flush()
        out: List[str] = []
        d = delim
        for fi, fld in enumerate(self.binned_fields):
            for ki, cv in enumerate(self.class_values):
                for b in range(self.bins[fi]):
                    c = int(self.post_counts[fi, ki, b])
                    if c == 0:
                        continue
                    blabel = fld.cardinality[b] if fld.is_categorical else str(b)
                    out.append(f"{cv}{d}{fld.ordinal}{d}{blabel}{d}{c}")
                    out.append(f"{cv}{d}{d}{d}{c}")
                    out.append(f"{d}{fld.ordinal}{d}{blabel}{d}{c}")
        for fi, fld in enumerate(self.cont_fields):
            for ki, cv in enumerate(self.class_values):
                cnt, s, sq = self.cont_moments[fi, ki]
                if cnt <= 0:
                    continue
                mean = s / cnt
                var = (sq - cnt * mean * mean) / max(cnt - 1, 1.0)
                std = math.sqrt(max(var, 0.0))
                out.append(f"{cv}{d}{fld.ordinal}{d}{d}{mean:.6f}{d}{std:.6f}")
                out.append(f"{cv}{d}{d}{d}{int(cnt)}")
            pm = self.cont_moments[fi].sum(axis=0)
            pmean = pm[1] / max(pm[0], 1.0)
            pvar = (pm[2] - pm[0] * pmean * pmean) / max(pm[0] - 1, 1.0)
            out.append(
                f"{d}{fld.ordinal}{d}{d}{pmean:.6f}{d}{math.sqrt(max(pvar, 0.0)):.6f}"
            )
        return "\n".join(out) + "\n"

    def save(self, path: str, delim: str = ",", stamp: bool = True) -> None:
        """``stamp`` publishes the format/digest sidecar the serving
        path verifies at load (models/artifact.py)."""
        with open(path, "w") as fh:
            fh.write(self.to_csv(delim))
        if stamp:
            from avenir_tpu.models.artifact import write_stamp
            write_stamp(path)

    @classmethod
    def load(cls, path: str, schema: FeatureSchema, delim: str = ",") -> "NaiveBayesModel":
        from avenir_tpu.models.artifact import verify_stamp
        verify_stamp(path)
        # the model file is self-describing (the reference's BayesianModel
        # is built from the file alone, BayesianPredictor.java:332-340):
        # class values and categorical feature bins it mentions extend any
        # data-discovered vocabularies a freshly-loaded schema lacks,
        # in file order so codes match the training-side discovery
        cat_need = {f.ordinal: f for f in schema.fields
                    if f.is_categorical and not f.cardinality
                    and not f.id_field}
        if cat_need:
            cls_fld = schema.class_field
            cls_ord = cls_fld.ordinal if cls_fld is not None else None
            seen: Dict[int, List[str]] = {o: [] for o in cat_need}
            with open(path) as fh:
                for line in fh:
                    items = line.rstrip("\n").split(delim)
                    if len(items) < 4:
                        continue
                    cv, o, b = items[0], items[1], items[2]
                    if cv and cls_ord in seen and cv not in seen[cls_ord]:
                        seen[cls_ord].append(cv)
                    if o and b:
                        ordn = int(o)
                        if ordn in seen and ordn != cls_ord \
                                and b not in seen[ordn]:
                            seen[ordn].append(b)
            for o, fld in cat_need.items():
                if seen[o]:
                    fld.cardinality = seen[o]
                    fld.discovered_cardinality = True
        model = cls.empty(schema)
        bin_index = {f.ordinal: i for i, f in enumerate(model.binned_fields)}
        cont_index = {f.ordinal: i for i, f in enumerate(model.cont_fields)}
        cls_index = {v: i for i, v in enumerate(model.class_values)}
        k = len(model.class_values)
        if model.cont_fields:
            model.cont_params = np.zeros((len(model.cont_fields), k, 2))
            model.cont_prior_params = np.zeros((len(model.cont_fields), 2))
        class_counts = np.zeros_like(model.class_counts)
        with open(path) as fh:
            for line in fh:
                items = line.rstrip("\n").split(delim)
                if len(items) < 4:
                    continue
                cv, o, b = items[0], items[1], items[2]
                if cv == "" and o != "":
                    if b == "":  # continuous feature prior: ,ord,,mean,std
                        fi = cont_index[int(o)]
                        model.cont_prior_params[fi] = [float(items[3]), float(items[4])]
                    # binned feature priors re-derive from posteriors
                elif cv != "" and o == "" and b == "":
                    # class prior rows: reference emits one per reduce group and
                    # sums on load (BayesianModel.addClassPrior); normalization
                    # cancels the duplication
                    class_counts[cls_index[cv]] += float(items[3])
                elif cv != "" and o != "":
                    ordn = int(o)
                    ki = cls_index[cv]
                    if b != "":  # binned posterior
                        fi = bin_index[ordn]
                        fld = model.binned_fields[fi]
                        code = (
                            fld.cardinality_index()[b]
                            if fld.is_categorical
                            else int(b)
                        )
                        model.post_counts[fi, ki, code] += float(items[3])
                    else:  # continuous posterior: classVal,ord,,mean,std
                        fi = cont_index[ordn]
                        model.cont_params[fi, ki] = [float(items[3]), float(items[4])]
        model.class_counts = class_counts
        return model


@partial(jax.jit, static_argnames=("k", "bmax"))
def _count_batch_kernel(codes, labels, x_cont, w, k: int, bmax: int):
    oh_k = jax.nn.one_hot(labels, k, dtype=jnp.float32) * w[:, None]   # [n,K]
    oh_b = jax.nn.one_hot(codes, bmax, dtype=jnp.float32)              # [n,F,B]
    post = jnp.einsum("nk,nfb->fkb", oh_k, oh_b)
    trip = jnp.stack(
        [jnp.ones_like(x_cont), x_cont, x_cont * x_cont], axis=-1
    )                                                                  # [n,Fc,3]
    mom = jnp.einsum("nk,nfm->fkm", oh_k, trip)
    cls = oh_k.sum(axis=0)
    return post, mom, cls


def _count_batch(codes, labels, x_cont, k: int, bmax: int, weights=None):
    n = labels.shape[0]
    w = weights if weights is not None else jnp.ones((n,), jnp.float32)
    return _count_batch_kernel(codes, labels, x_cont, w, k, bmax)


@partial(jax.jit, static_argnames=("k", "bmax"), donate_argnums=(0,))
def _fold_batch_kernel(acc, codes, labels, x_cont, w, k: int, bmax: int):
    batch = _count_batch_kernel(codes, labels, x_cont, w, k, bmax)
    # per-batch einsum counts are <= batch rows, exact in f32; the fold
    # target's dtype (int32 on the unweighted path) sets the ceiling
    return jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc, batch)


def _table_at(table, idx):
    """table[f, idx[i, f]] as [n, F], for a table [F, J] and idx [n, F] or
    [n, 1]: a select for each of the J columns. Exact, fused into the
    pass over the rows, and no gather (whose index tensor alone is 10 GB
    at 21M rows on a TPU)."""
    out = jnp.broadcast_to(table[:, 0], idx.shape[:1] + table.shape[:1])
    for j in range(1, table.shape[1]):
        out = jnp.where(idx == j, table[:, j], out)
    return out


@jax.jit
def _feature_prob_kernel(codes, labels, x_cont, tables):
    """P(features | own class) of every row, float32 [n]: the product over
    binned features of P(bin | class y) and over continuous features of
    the Gaussian density under class y. One pass over the rows; a row's
    own class alone, never the [n, Fc, K] densities that `predict` builds.
    A weight under float32's least normal number reads 0.0 where the
    device flushes subnormals, and `knn._vote` then leaves that
    neighbour's score unweighted."""
    own = labels[:, None]
    logp = jnp.zeros(labels.shape, jnp.float32)
    if codes.shape[1]:
        log_post = tables["log_post"]                               # [F, K, B]
        logp += _table_at(log_post.reshape(log_post.shape[0], -1),
                          own * log_post.shape[2] + codes).sum(axis=1)
    if x_cont.shape[1]:
        mean, std = tables["cont_mean"], tables["cont_std"]         # [Fc, K]
        # what does not depend on the row is summed per class first, so
        # the per-row sum stays small and rounds little in float32
        const = -(0.5 * math.log(2 * math.pi) * mean.shape[0]
                  + jnp.log(std).sum(axis=0, keepdims=True))        # [1, K]
        z = (x_cont - _table_at(mean, own)) / _table_at(std, own)   # [n, Fc]
        logp += _table_at(const, own)[:, 0] - 0.5 * (z * z).sum(axis=1)
    return jnp.exp(logp)


class NaiveBayesPredictor:
    """Jitted posterior computation + arbitration over a finished model."""

    def __init__(
        self,
        model: NaiveBayesModel,
        arbitrator: Optional[CostBasedArbitrator] = None,
    ):
        self.model = model
        self.tables = model.finish()
        self.arbitrator = arbitrator

        @jax.jit
        def predict(codes, x_cont, tables):
            # binned: log P(F|C) = sum_f log_post[f, :, code_f]; einsum over
            # one-hot keeps it on the MXU.
            parts = []
            if codes.shape[1] > 0:
                oh = jax.nn.one_hot(codes, tables["log_post"].shape[2],
                                    dtype=jnp.float32)          # [n,F,B]
                lp = jnp.einsum("nfb,fkb->nk", oh, tables["log_post"])
                lprior = jnp.einsum("nfb,fb->n", oh, tables["log_prior"])
                parts.append((lp, lprior))
            if x_cont.shape[1] > 0:
                mean, std = tables["cont_mean"], tables["cont_std"]        # [Fc,K]
                x = x_cont[:, :, None]                                      # [n,Fc,1]
                logpdf = (
                    -0.5 * jnp.log(2 * jnp.pi)
                    - jnp.log(std)[None]
                    - 0.5 * ((x - mean[None]) / std[None]) ** 2
                )                                                           # [n,Fc,K]
                lp = logpdf.sum(axis=1)
                pmean, pstd = tables["cont_prior_mean"], tables["cont_prior_std"]
                logpdf_pr = (
                    -0.5 * jnp.log(2 * jnp.pi)
                    - jnp.log(pstd)[None]
                    - 0.5 * ((x_cont - pmean[None]) / pstd[None]) ** 2
                )
                parts.append((lp, logpdf_pr.sum(axis=1)))
            log_feat_c = sum(p[0] for p in parts)
            log_feat = sum(p[1] for p in parts)
            log_post = log_feat_c + tables["log_class"][None, :] - log_feat[:, None]
            prob_pct = jnp.floor(jnp.exp(log_post) * 100.0).astype(jnp.int32)
            pred = jnp.argmax(prob_pct, axis=1)
            return pred, prob_pct

        self._predict = predict

    def predict(self, dataset: Dataset) -> Tuple[np.ndarray, np.ndarray]:
        codes, _ = dataset.feature_codes(self.model.binned_fields)
        x_cont = dataset.feature_matrix(self.model.cont_fields)
        pred, prob = self._predict(jnp.asarray(codes), jnp.asarray(x_cont),
                                   self.tables)
        pred, prob = np.asarray(pred), np.asarray(prob)
        if self.arbitrator is not None and len(self.model.class_values) == 2:
            neg = self.model.class_values.index(self.arbitrator.neg_class)
            pos = 1 - neg
            is_pos = self.arbitrator.arbitrate(prob[:, neg], prob[:, pos])
            pred = np.where(is_pos, pos, neg).astype(pred.dtype)
        return pred, prob

    def validate(self, dataset: Dataset, pos_class: int = 0) -> ConfusionMatrix:
        pred, _ = self.predict(dataset)
        cm = ConfusionMatrix(self.model.class_values, pos_class=pos_class)
        cm.add(dataset.labels(), pred)
        return cm

    @classmethod
    def fit_feature_prob(cls, dataset: Dataset,
                         labels: Optional[jnp.ndarray] = None
                         ) -> Tuple["NaiveBayesPredictor", jnp.ndarray]:
        """A predictor fitted on `dataset`, and `feature_prob_device` of
        the same rows: they go to the device once and serve the fold and
        the posterior. `labels` are the dataset's, where the caller has
        them on the device already."""
        with obs.span("nb.fit", rows=len(dataset)):
            model = NaiveBayesModel.empty(dataset.schema)
            codes, _ = dataset.feature_codes(model.binned_fields)
            rows = (jnp.asarray(codes),
                    jnp.asarray(dataset.labels()) if labels is None else labels,
                    jnp.asarray(dataset.feature_matrix(model.cont_fields)))
            model.accumulate(*rows)
            predictor = cls(model)
        return predictor, predictor.feature_prob_device(dataset, rows)

    def feature_prob(self, dataset: Dataset) -> np.ndarray:
        """Per-row P(features | actual class): the bap.output.feature.prob.only
        mode whose output the reference's KNN pipeline joins as
        class-conditional weights (BayesianPredictor.java:262-286).
        float32 [n], computed on the device and fetched."""
        return np.asarray(self.feature_prob_device(dataset))

    def feature_prob_device(self, dataset: Dataset,
                            rows: Optional[tuple] = None) -> jnp.ndarray:
        """The same, left on the device as dispatched. `rows` are the
        dataset's (codes, labels, x_cont) as `accumulate` takes them,
        where the caller has them on the device already."""
        with obs.span("nb.feature_prob", rows=len(dataset),
                      binned=len(self.model.binned_fields),
                      continuous=len(self.model.cont_fields)):
            with obs.span("nb.feature_prob.binned"):
                if rows is None:
                    codes, _ = dataset.feature_codes(self.model.binned_fields)
                    labels = dataset.labels()
                else:
                    codes, labels = rows[:2]
            with obs.span("nb.feature_prob.continuous"):
                # a matrix made here is a temporary of the call, so that
                # its release falls inside the span as its making does
                return _feature_prob_kernel(
                    jnp.asarray(codes), jnp.asarray(labels),
                    jnp.asarray(dataset.feature_matrix(self.model.cont_fields))
                    if rows is None else rows[2], self.tables)
