"""K-nearest-neighbor classifier/regressor: the 5-job pipeline fused.

Reference flow (resource/knn.sh:44-132, SURVEY §3.3): (1) external sifarish
SameTypeSimilarity computes all-pairs train-test distances; (2-3) Bayesian
jobs compute per-train-entity feature posterior probabilities; (4) a join MR
attaches them to the distance file; (5) NearestNeighbor re-keys with
secondary sort so the reducer sees distance-ranked neighbors and votes
(knn/NearestNeighbor.java, knn/Neighborhood.java).

Here all five jobs are one device program per test batch: blocked streaming
top-k over the train set (ops.distance), kernel scores, and a one-hot
matmul vote — with the class-conditional weighting computed directly from a
NaiveBayesModel instead of a file join.

Kernel semantics follow Neighborhood.processClassDitribution
(Neighborhood.java:150-218) with KERNEL_SCALE=100 and int-floored scores;
distances are mapped to the reference's int scale (0..100) first:
  none                 score = 1
  linearMultiplicative score = d==0 ? 200 : floor(100/d)
  linearAdditive       score = 100 - d
  gaussian             score = floor(100 * exp(-0.5 (d/param)^2))
Class-conditional weighting multiplies each neighbor's score by its feature
posterior prob (Neighbor.setScore, :393-404), optionally by 1/d (inverse
distance). Classification = arg-max class score, or decision-threshold
pos/neg ratio test (classify(), :272-312). Regression = average / median /
per-query simple linear regression over the neighbors (doRegression(),
:223-250).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from avenir_tpu import obs
from avenir_tpu.core.dataset import Dataset, pad_rows
from avenir_tpu.models.naive_bayes import NaiveBayesModel
from avenir_tpu.native import ingest
from avenir_tpu.ops.distance import blocked_topk_neighbors, pad_train
from avenir_tpu.utils.metrics import ConfusionMatrix

KERNEL_SCALE = 100

KERNELS = ("none", "linearMultiplicative", "linearAdditive", "gaussian")

#: query rows per grid step of the pallas kernels; queries pad up to it
_BLOCK_Q = 256


from avenir_tpu.core.dataset import extract_mixed_features as _extract
from avenir_tpu.core.dataset import mixed_feature_columns


def _nbytes(*arrays) -> int:
    """Bytes of the arrays that are there."""
    return sum(int(a.nbytes) for a in arrays if a is not None)


def _expand_mixed(x_num, ranges, x_cat, bins, metric: str):
    """One-hot-expand categoricals into the numeric matrix so the MIXED
    metric rides the numeric pallas kernels: a one-hot pair contributes
    ||a-b||^2 = 2*[a != b] (and L1 = 2*[a != b]), so scaling the one-hot
    by 1/sqrt(2) (euclidean) or 1/2 (manhattan) makes the kernel's summed
    term exactly the hamming mismatch count of ops.distance's mixed
    semantics. The caller divides by the SEMANTIC attribute count
    (n_attrs) instead of the expanded column count. `kernel_matrix`
    writes these rows in one native pass; this stacked form is its
    fallback and its tests' oracle."""
    n = x_num.shape[0] if x_num is not None else x_cat.shape[0]
    cols = []
    if x_num is not None and x_num.shape[1]:
        cols.append(np.asarray(x_num, np.float32)
                    / np.maximum(np.asarray(ranges, np.float32), 1e-9))
    scale = _onehot_scale(metric)
    rows = np.arange(n, dtype=np.int32)
    for f, b in enumerate(bins or ()):
        oh = np.zeros((n, b), np.float32)
        oh[rows, np.asarray(x_cat[:, f], np.int32)] = scale
        cols.append(oh)
    x = np.concatenate(cols, axis=1) if cols else np.zeros((n, 0), np.float32)
    n_attrs = (x_num.shape[1] if x_num is not None else 0) + len(bins or ())
    return x, n_attrs


def _cat_names(ds: Dataset) -> Tuple[str, ...]:
    """The dataset's categorical features' names, in column order."""
    return tuple(f.name for f in ds.schema.feature_fields if f.is_categorical)


def _onehot_scale(metric: str) -> np.float32:
    """A one-hot entry's value: a mismatched pair then adds exactly 1 to
    the kernel's sum (see `_expand_mixed`)."""
    return np.float32((1.0 / np.sqrt(2.0)) if metric == "euclidean" else 0.5)


def kernel_matrix(num, ranges, cats, bins, metric: str, multiple: int,
                  threads: int = 0, cat_names=()) -> Tuple[np.ndarray, bool]:
    """The pallas kernels' input from a dataset's columns
    (`mixed_feature_columns`): float32 [n padded to `multiple`, width],
    `_expand_mixed`'s normalised, one-hot-expanded rows, then zero rows.
    Written in one native pass striped over the rows
    (`native.ingest.knn_index_matrix_native`); where the native library
    is not built, `_expand_mixed` and `pad_train` make the same bytes.
    Returns (matrix, whether the native pass wrote it)."""
    n = len(num[0]) if num else len(cats[0]) if cats else 0
    if ingest.native_available():
        out = np.empty((pad_rows(n, multiple), len(num) + sum(bins or ())),
                       np.float32)
        ingest.knn_index_matrix_native(num, ranges, cats, bins or (),
                                       _onehot_scale(metric), out,
                                       threads=threads, cat_names=cat_names)
        return out, True
    x_num = (np.stack(num, axis=1) if num
             else np.zeros((n, 0), np.float32))
    x_cat = np.stack(cats, axis=1) if cats else None
    x, _ = _expand_mixed(x_num, ranges, x_cat, bins, metric)
    return pad_train(x, None, multiple)[0], False


@partial(jax.jit, static_argnames=("kernel", "num_classes", "class_cond",
                                   "inverse_weighted"))
def _vote(
    dist: jnp.ndarray,            # [nq, k] raw distances in [0, ~1]
    neigh_labels: jnp.ndarray,    # [nq, k] int class codes
    neigh_post: jnp.ndarray,      # [nq, k] feature posterior probs (or ones)
    kernel: str,
    kernel_param: float,
    num_classes: int,
    class_cond: bool,
    inverse_weighted: bool,
):
    d = jnp.floor(dist * KERNEL_SCALE)          # reference's int distance scale
    if kernel == "none":
        score = jnp.ones_like(d)
    elif kernel == "linearMultiplicative":
        score = jnp.where(d == 0, 2.0 * KERNEL_SCALE, jnp.floor(KERNEL_SCALE / jnp.maximum(d, 1.0)))
    elif kernel == "linearAdditive":
        # clamp at 0: distances can exceed the normalized range when test
        # values fall outside the schema's declared [min, max], and a
        # negative score would subtract votes from the neighbor's class
        score = jnp.maximum(KERNEL_SCALE - d, 0.0)
    elif kernel == "gaussian":
        t = d / kernel_param
        score = jnp.floor(KERNEL_SCALE * jnp.exp(-0.5 * t * t))
    else:
        raise ValueError(f"unknown kernel {kernel}")

    if class_cond:
        w = jnp.where(neigh_post > 0, score * neigh_post, score)
        if inverse_weighted:
            w = w / jnp.maximum(d, 1.0)
        score = w

    # unfilled neighbor slots (dist=inf, idx=-1 sentinel) contribute nothing
    score = jnp.where(jnp.isfinite(dist), score, 0.0)
    oh = jax.nn.one_hot(neigh_labels, num_classes, dtype=jnp.float32)
    class_scores = jnp.einsum("qk,qkc->qc", score.astype(jnp.float32), oh)
    return class_scores


class NeighborIndex:
    """Streaming nearest-neighbor search over a train Dataset — the part of
    the pipeline that replaces sifarish. Label-free: usable for regression
    and clustering datasets whose schema has no class attribute."""

    def __init__(
        self,
        train: Dataset,
        k: int = 5,
        metric: str = "manhattan",
        block: int = 4096,
        approx: bool = False,
        use_pallas: Optional[bool] = None,
        packed: bool = False,
    ):
        """packed=True opts into the lane-resident packed-key kernel
        (ops.pallas_knn.knn_topk_lanes) — several times faster, but
        distances are quantized to ~2^-13 relative, which can reorder
        near-tied neighbors. The default (packed=False) keeps the exact
        kernel so TPU results match the jnp/reference path bit-for-bit
        modulo f32 dot-form error."""
        attrs = sum(f.is_numeric or f.is_categorical
                    for f in train.schema.feature_fields)
        with obs.span("knn.index.build", rows=len(train), attrs=attrs) as note:
            self._build(train, k, metric, block, approx, use_pallas, packed)
            note.update(padded_rows=self.n_padded,
                        nbytes=_nbytes(self.t_num, self.t_cat))

    def _build(self, train: Dataset, k: int, metric: str, block: int,
               approx: bool, use_pallas: Optional[bool],
               packed: bool) -> None:
        self.schema = train.schema
        # the reference takes "the first topMatchCount values" — a train set
        # smaller than k just yields all of it
        self.k = max(1, min(k, len(train)))
        self.metric = metric
        self.approx = approx
        self.block = min(block, max(len(train), 1))

        # the pallas kernels serve numeric AND mixed data on real TPU (the
        # flop-heavy sifarish role): categoricals one-hot-expand into the
        # numeric matrix (_expand_mixed) so the hamming term is matmul work
        from avenir_tpu.ops.pallas_knn import pallas_available

        has_features = any(f.is_numeric or f.is_categorical
                           for f in train.schema.feature_fields)
        if use_pallas:
            # explicit opt-in still requires the kernel's preconditions
            if not pallas_available():
                raise RuntimeError(
                    "pallas KNN kernel needs a TPU backend "
                    "(jax.default_backend() != 'tpu')")
            if not has_features:
                raise ValueError("pallas KNN kernel: schema has no features")
            if metric not in ("euclidean", "manhattan"):
                raise ValueError(f"pallas KNN kernel: unsupported metric {metric!r}")
            if approx:
                raise ValueError(
                    "the pallas KNN kernels compute full (non-approximate) "
                    "top-k; approx=True needs the jnp path (approx_min_k)")
        self.use_pallas = (
            use_pallas if use_pallas is not None
            else (pallas_available() and has_features
                  and metric in ("euclidean", "manhattan") and not approx)
        )
        self.packed = packed and self.use_pallas
        self.n_attrs = None
        if self.use_pallas:
            # normalize + one-hot-expand once, padded to the kernel block,
            # straight from the dataset's columns into the matrix the put
            # takes. 256x8192 f32 tile = 8 MB VMEM, the measured sweet
            # spot; the lane-packed kernel carries global chunk ids so
            # block_t has no index-bit cap (corpus cap 524288 rows
            # enforced by the kernel). 256-row granularity: the lane
            # kernel's pair-fold front end requires block_t % 256 == 0
            # (the exact kernel only needs 128, but a 128-odd block would
            # crash the packed path)
            self.block = max(256, min(pad_rows(len(train), 256), 8192))
            with obs.span("knn.index.extract"):
                num, ranges, cats, bins = mixed_feature_columns(train)
            with obs.span("knn.index.expand", threads=0) as note:
                t_num, note["native"] = kernel_matrix(
                    num, ranges, cats, bins, metric, self.block,
                    cat_names=_cat_names(train))
                note["nbytes"] = t_num.nbytes
            self.n_attrs = len(num) + len(cats)
            x_cat, n_valid = None, len(train)
        else:
            with obs.span("knn.index.extract"):
                x_num, ranges, x_cat, bins = _extract(train)
            with obs.span("knn.index.pad"):
                t_num, x_cat, n_valid = pad_train(x_num, x_cat, self.block)
        self._expand_ranges = ranges
        # the cap is a static property of the corpus: decide the packed
        # routing once here, not per query (beyond the lane kernel's
        # packed-chunk-id cap the exact kernel serves — explicit index
        # carries, no cap)
        if self.packed and t_num is not None:
            from avenir_tpu.ops.pallas_knn import LANE_CORPUS_CAP

            self.packed = t_num.shape[0] <= LANE_CORPUS_CAP
        with obs.span("knn.index.put") as note:
            issued = obs.now()
            self.t_num = jnp.asarray(t_num) if t_num is not None else None
            self.t_cat = jnp.asarray(x_cat) if x_cat is not None else None
            self.ranges = jnp.asarray(ranges) if ranges.size else None
            note["nbytes"] = _nbytes(self.t_num, self.t_cat)
        obs.landed("knn.index.put.landed",
                   (self.t_num, self.t_cat, self.ranges), issued)
        self.cat_bins = bins
        self.n_valid = n_valid
        self.n_padded = (
            self.t_num.shape[0] if self.t_num is not None else self.t_cat.shape[0]
        )

    @property
    def kernel(self) -> str:
        """Which top-k route serves this index's queries."""
        if not self.use_pallas:
            return "jnp"
        return "packed" if self.packed else "exact"

    def queries(self, test: Dataset) -> Tuple:
        """A test block as the search takes it: (q_num, q_cat, nq). On the
        pallas route q_num is normalized, one-hot-expanded and padded to
        the kernels' 256-row query block, and q_cat is None."""
        if not self.use_pallas:
            q_num, _, q_cat, _ = _extract(test)
            return q_num, q_cat, len(test)
        num, _, cats, _ = mixed_feature_columns(test)
        q, _ = kernel_matrix(num, self._expand_ranges, cats, self.cat_bins,
                             self.metric, _BLOCK_Q,
                             cat_names=_cat_names(test))
        return q, None, len(test)

    def search(self, queries: Tuple) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(dist [nq,k], train index [nq,k]) of prepared `queries`, as
        dispatched; unfillable slots are (+inf, -1)."""
        return self.search_counted(queries)[:2]

    def search_counted(self, queries: Tuple) -> Tuple[
            jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
        """`search`, and where the exact kernel serves, per query block
        its count of the train slices it extracted (slices of
        ops.pallas_knn.slice_rows(self.block) rows), as dispatched with
        the rest; None on the other routes."""
        q_num, q_cat, nq = queries
        if self.use_pallas:
            from avenir_tpu.ops.pallas_knn import knn_topk_lanes, knn_topk_pallas

            topk = knn_topk_lanes if self.packed else knn_topk_pallas
            dist, idx, *extracted = topk(
                jnp.asarray(q_num), self.t_num, k=self.k, block_q=_BLOCK_Q,
                block_t=self.block, metric=self.metric,
                n_valid=self.n_valid, n_attrs=self.n_attrs)
            return dist[:nq], idx[:nq], extracted[0] if extracted else None
        dist, idx = blocked_topk_neighbors(
            jnp.asarray(q_num) if self.t_num is not None else None,
            self.t_num,
            jnp.asarray(q_cat) if self.t_cat is not None else None,
            self.t_cat,
            cat_bins=self.cat_bins,
            num_ranges=self.ranges,
            k=self.k,
            block=self.block,
            metric=self.metric,
            n_valid=self.n_valid,
            approx=self.approx,
        )
        return dist, idx, None

    def slice_counts(self, extracted: jnp.ndarray) -> Dict[str, int]:
        """What `search_counted`'s third value says, fetched: the train
        slices the exact kernel tested (query blocks x slices of the
        padded corpus), those it extracted, and the rows of a slice."""
        from avenir_tpu.ops.pallas_knn import slice_rows

        width = slice_rows(self.block)
        return {"slices": extracted.shape[0] * (self.n_padded // width),
                "extracted": int(np.asarray(extracted).sum()),
                "slice_rows": width}

    def neighbors(self, test: Dataset) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(dist [nq,k], train index [nq,k]); unfillable slots are (+inf, -1)."""
        return self.search(self.queries(test))

    def classify_scores(self, queries: Tuple, train_labels: jnp.ndarray,
                        n_classes: int, kernel_fn: str,
                        kernel_param: float) -> Optional[jnp.ndarray]:
        """Fully fused device classification of prepared `queries`:
        kernel-weighted top-k vote scores [nq, C] via
        ops.pallas_knn.knn_classify_lanes — the top-k results never leave
        the kernel (non-class-conditional vote modes). Returns None when
        this index can't serve the fused path (jnp route, or a block too
        small for the lane kernel's pair fold)."""
        if not self.use_pallas or self.block % 256 != 0:
            return None
        from avenir_tpu.ops.pallas_knn import knn_classify_lanes

        q, _, nq = queries
        scores = knn_classify_lanes(
            jnp.asarray(q), self.t_num, train_labels, k=self.k,
            n_classes=n_classes, n_attrs=self.n_attrs,
            kernel_fn=kernel_fn, kernel_param=kernel_param,
            block_q=_BLOCK_Q, block_t=self.block, metric=self.metric,
            n_valid=self.n_valid)
        return scores[:nq]


class NearestNeighborClassifier:
    """nen.* job equivalent. Parameters mirror the knn.properties keys."""

    def __init__(
        self,
        train: Dataset,
        top_match_count: int = 5,
        kernel_function: str = "none",
        kernel_param: float = 1.0,
        class_cond_weighted: bool = False,
        inverse_distance_weighted: bool = False,
        decision_threshold: float = -1.0,
        positive_class: Optional[str] = None,
        metric: str = "manhattan",
        block: int = 4096,
        nb_model: Optional[NaiveBayesModel] = None,
        approx: bool = False,
        fused: bool = False,
        packed: bool = False,
    ):
        """fused=True opts into the in-kernel vote (knn_classify_lanes) for
        the non-class-conditional modes: class scores come straight out of
        the pallas kernel (distances quantized ~2^-21, ties biased toward
        lower class codes). packed=True opts the top-k side into the
        lane-resident packed-key kernel (NeighborIndex). The default
        composes the exact top-k with the jitted _vote."""
        self.index = NeighborIndex(train, k=top_match_count, metric=metric,
                                   block=block, approx=approx, packed=packed)
        self.fused = fused
        self.schema = train.schema
        self.k = self.index.k
        self.kernel = kernel_function
        self.kernel_param = kernel_param
        self.class_cond = class_cond_weighted
        self.inverse_weighted = inverse_distance_weighted
        self.decision_threshold = decision_threshold
        self.class_values = train.schema.class_values()
        self.positive_class = (
            self.class_values.index(positive_class) if positive_class else 1
        )
        pad = self.index.n_padded
        n_valid = self.index.n_valid
        with obs.span("knn.index.put") as note:
            labels = np.zeros((pad,), np.int32)
            labels[:n_valid] = train.labels()
            issued = obs.now()
            self.train_labels = jnp.asarray(labels)
            note["nbytes"] = labels.nbytes
        obs.landed("knn.index.put.landed", self.train_labels, issued)

        # class-conditional weighting: P(features_i | class_i) per train row,
        # the quantity jobs (2)-(4) of the reference pipeline compute + join
        # (BayesianPredictor bap.output.feature.prob.only=true mode) — the
        # same NaiveBayesPredictor.feature_prob the file-based job emits,
        # made on the device and left there
        post = None
        if class_cond_weighted:
            from avenir_tpu.models.naive_bayes import NaiveBayesPredictor

            if nb_model is not None:
                post = NaiveBayesPredictor(nb_model).feature_prob_device(train)
            else:
                # the train rows go to the device once, for the fold and
                # the posterior, and are dropped on return: nothing of
                # them stands beside the top-k kernel's scratch (a slice
                # of the whole is the array itself, no copy)
                _, post = NaiveBayesPredictor.fit_feature_prob(
                    train, self.train_labels[:n_valid])
        with obs.span("knn.index.put") as note:
            # ones where nothing weights a row, and beyond the last row
            if post is None:
                post = jnp.ones((pad,), jnp.float32)
            elif n_valid < pad:
                post = jnp.concatenate(
                    [post, jnp.ones((pad - n_valid,), jnp.float32)])
            self.train_post = post
            note["nbytes"] = post.nbytes

    # ------------------------------------------------------------- neighbors
    def neighbors(self, test: Dataset) -> Tuple[np.ndarray, np.ndarray]:
        """(dist [nq,k], train index [nq,k]) over the real train rows."""
        return self.index.neighbors(test)

    # --------------------------------------------------------------- predict
    def predict(self, test: Dataset) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (predicted class codes [nq], class scores [nq, K])."""
        with obs.span("knn.query.prepare", rows=len(test)):
            queries = self.index.queries(test)
        # as dispatched: nothing here waits for the device, the fetch does
        with obs.span("knn.query.dispatch", rows=len(test)) as note:
            scores = None
            if self.fused and not self.class_cond:
                scores = self.index.classify_scores(
                    queries, self.train_labels, len(self.class_values),
                    self.kernel, self.kernel_param)
            note["kernel"] = "fused" if scores is not None else self.index.kernel
            extracted = None
            if scores is None:
                dist, idx, extracted = self.index.search_counted(queries)
                neigh_labels = self.train_labels[idx]
                neigh_post = self.train_post[idx]
                scores = _vote(
                    dist, neigh_labels, neigh_post,
                    self.kernel, self.kernel_param, len(self.class_values),
                    self.class_cond, self.inverse_weighted,
                )
        with obs.span("knn.query.fetch", rows=len(test)) as note:
            scores = np.asarray(scores)
            if extracted is not None:
                note.update(self.index.slice_counts(extracted))
        # the reference's threshold branch exists only in non-class-cond mode
        # (Neighborhood.classify(), :272-312: weighted path pure-argmaxes)
        if (self.decision_threshold > 0 and len(self.class_values) == 2
                and not self.class_cond):
            pos = self.positive_class
            neg = 1 - pos
            ratio = scores[:, pos] / np.maximum(scores[:, neg], 1e-9)
            pred = np.where(ratio > self.decision_threshold, pos, neg).astype(np.int32)
        else:
            pred = scores.argmax(axis=1).astype(np.int32)
        return pred, scores

    def validate(self, test: Dataset, pos_class: Optional[int] = None) -> ConfusionMatrix:
        pred, _ = self.predict(test)
        cm = ConfusionMatrix(
            self.class_values,
            pos_class=self.positive_class if pos_class is None else pos_class,
        )
        cm.add(test.labels(), pred)
        return cm


class NearestNeighborRegressor:
    """Regression modes of Neighborhood.doRegression: average / median /
    per-query simple linear regression (commons-math3 SimpleRegression
    equivalent via closed-form least squares, vmap'd over queries)."""

    def __init__(
        self,
        train: Dataset,
        target: np.ndarray,
        top_match_count: int = 5,
        method: str = "average",
        regr_input: Optional[np.ndarray] = None,
        metric: str = "manhattan",
        block: int = 4096,
    ):
        self.index = NeighborIndex(train, k=top_match_count, metric=metric,
                                   block=block)
        pad = self.index.n_padded
        t = np.zeros((pad,), np.float32)
        t[: len(target)] = np.asarray(target, np.float32)
        self.target = jnp.asarray(t)
        self.method = method
        if regr_input is not None:
            ri = np.zeros((pad,), np.float32)
            ri[: len(regr_input)] = np.asarray(regr_input, np.float32)
            self.regr_input = jnp.asarray(ri)
        else:
            self.regr_input = None

    def predict(self, test: Dataset,
                query_input: Optional[np.ndarray] = None) -> np.ndarray:
        dist, idx = self.index.neighbors(test)
        y = self.target[idx]                                    # [nq, k]
        if self.method == "average":
            return np.asarray(y.mean(axis=1))
        if self.method == "median":
            return np.asarray(jnp.median(y, axis=1))
        if self.method == "linearRegression":
            assert self.regr_input is not None and query_input is not None
            x = self.regr_input[idx]                            # [nq, k]
            xm = x.mean(axis=1, keepdims=True)
            ym = y.mean(axis=1, keepdims=True)
            cov = ((x - xm) * (y - ym)).sum(axis=1)
            var = ((x - xm) ** 2).sum(axis=1)
            slope = cov / jnp.maximum(var, 1e-9)
            intercept = ym[:, 0] - slope * xm[:, 0]
            return np.asarray(intercept + slope * jnp.asarray(query_input))
        raise ValueError(f"unknown regression method {self.method}")
