"""Exploration / feature-selection suite (org.avenir.explore re-designed).

Every job in the reference package is a contingency-table or moment
reduction over records: mutual information + selection scores
(MutualInformation.java, MutualInformationScore.java), Cramér / categorical
/ heterogeneity-reduction / numerical correlation, Relief feature relevance,
per-value class affinity, supervised categorical->continuous encoding,
class-balancing samplers. On TPU each is one or two one-hot einsum
contractions (cross_count) producing small count tensors, with the greedy
selection loops on host over those tiny tables.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureField
from avenir_tpu.ops.infotheory import (bits_entropy, entropy, gini,
                                       mutual_information,
                                       weighted_split_score)
from avenir_tpu.ops.reduce import cross_count, keyed_reduce

_EPS = 1e-12
# fused MI chunk keys are int32: past this keyspace they would wrap, so
# add() drops to per-pair cross_counts (each in its own small keyspace)
_FUSED_KEYSPACE_LIMIT = 2**31


def _padded_add(acc: Optional[np.ndarray], new: np.ndarray) -> np.ndarray:
    """acc + new where either may be smaller along any axis (growing
    data-discovered vocabularies); missing cells are zero counts."""
    if acc is None:
        return new
    if acc.shape == new.shape:
        return acc + new
    shape = tuple(max(a, b) for a, b in zip(acc.shape, new.shape))
    out = np.zeros(shape, np.float64)
    out[tuple(slice(0, s) for s in acc.shape)] += acc
    out[tuple(slice(0, s) for s in new.shape)] += new
    return out


# ---------------------------------------------------------------------------
# mutual information + feature selection scores
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("bmax", "k", "nf"))
def _mi_chunk_counts(codes, y, bmax: int, k: int, nf: int):
    """One chunk's complete MI count-table set in three keyed reductions:
    fc [F, bmax, k], pair [P, bmax, bmax] and pairc [P, bmax, bmax, k],
    P = F(F-1)/2 in upper-triangle order. int32 counts (exact to 2^31);
    peak memory is the [n, P] key tensor — pair analysis is inherently
    O(F^2) work either way, this shape just buys it with 3 dispatches
    instead of F^2. Caller guarantees the fused keyspace
    P*bmax^2*k < 2^31 (add() falls back to per-pair cross_count past
    that; int keys would wrap)."""
    n = codes.shape[0]

    def count(keys, num):
        return keyed_reduce(keys.reshape(-1),
                            jnp.ones((keys.size,), jnp.int32), num)

    f_idx = jnp.arange(nf, dtype=jnp.int32)[None, :]
    fc = count((f_idx * bmax + codes) * k + y[:, None],
               nf * bmax * k).reshape(nf, bmax, k)
    ii, jj = np.triu_indices(nf, 1)            # static under jit
    npair = len(ii)
    if npair == 0:
        return (fc, jnp.zeros((0, bmax, bmax), jnp.int32),
                jnp.zeros((0, bmax, bmax, k), jnp.int32))
    ci, cj = codes[:, ii], codes[:, jj]        # [n, P]
    p_idx = jnp.arange(npair, dtype=jnp.int32)[None, :]
    key_p = (p_idx * bmax + ci) * bmax + cj
    pair = count(key_p, npair * bmax * bmax).reshape(npair, bmax, bmax)
    pairc = count(key_p * k + y[:, None],
                  npair * bmax * bmax * k).reshape(npair, bmax, bmax, k)
    return fc, pair, pairc


def _mi_chunk_counts_host(codes, y, bmax: int, k: int, nf: int):
    """_mi_chunk_counts in host numpy. XLA:CPU lowers segment_sum to a
    SERIAL per-element scatter — ~3s per 1.2M-row chunk on a laptop-class
    core, 50x the parse cost, which made MI the limiter of the CPU
    streaming proxies (and of the shared-scan fan-out, where its fold
    shares the scan with NB + discriminant). Here each table is one
    np.bincount over a small per-table int32 keyspace: per-PAIR keys (no
    fused [n, P] key tensor — the giant temporaries, not the counting,
    dominate host time), vectorized and exact. The device kernel fuses
    pairs because a dispatch costs ~fixed latency; a numpy call doesn't.
    Counts are integers, so both paths produce bit-identical tables and
    chunk-layout invariance is unaffected."""
    codes = np.ascontiguousarray(codes, np.int32)
    y = np.asarray(y, np.int32)
    # one [n, F] class-fused key tensor: column f's key code*k + y IS
    # the fc table key and the low digits of every pair-class key, so
    # each pair costs one add + one bincount; the class-marginal pair
    # table is the exact integer sum of pairc over the class axis —
    # not a second bincount pass over n
    cy = codes * np.int32(k) + y[:, None]                       # [n, F]
    fc = np.empty((nf, bmax, k), np.int64)
    for f in range(nf):
        fc[f] = np.bincount(cy[:, f],
                            minlength=bmax * k).reshape(bmax, k)
    npair = nf * (nf - 1) // 2
    pair = np.empty((npair, bmax, bmax), np.int64)
    pairc = np.empty((npair, bmax, bmax, k), np.int64)
    p = 0
    for i in range(nf):
        ci_bk = codes[:, i] * np.int32(bmax * k)
        for j in range(i + 1, nf):
            pairc[p] = np.bincount(
                ci_bk + cy[:, j],
                minlength=bmax * bmax * k).reshape(bmax, bmax, k)
            pair[p] = pairc[p].sum(axis=2)
            p += 1
    return fc, pair, pairc


class MutualInformationAnalyzer:
    """MutualInformation MR job equivalent (MutualInformation.java:62).

    One device pass builds all the distributions the reducer held in memory
    (class, feature, feature-pair, feature-class, feature-pair-class,
    MutualInformation.java:138-216); the score algorithms are the greedy
    loops of MutualInformationScore.java over those tables:
      mutual.info.maximization (MIM)        :98
      mutual.info.selection (MIFS, beta)    :116-140
      joint.mutual.info (JMI)               :177
      double.input.symmetric.relevance(DISR):185-229
      min.redundancy.max.relevance (mRMR)   :265-288
    MI values are in nats (reference uses log base e via Math.log).
    """

    def __init__(self, ds: Optional[Dataset] = None):
        self.ds = ds
        self.fields: Optional[List[FeatureField]] = None
        self.bins: List[int] = []
        self.k = 0
        self.n = 0
        self._fc: List[np.ndarray] = []            # per f: [Bf, K]
        self._pair: Dict[Tuple[int, int], np.ndarray] = {}   # [Bi, Bj]
        self._pairc: Dict[Tuple[int, int], np.ndarray] = {}  # [Bi, Bj, K]
        if ds is not None:
            self.add(ds)
            self.finalize()

    @classmethod
    def from_chunks(cls, chunks) -> "MutualInformationAnalyzer":
        """Build from streamed Dataset chunks: every distribution the
        reducer held (MutualInformation.java:138-216) is an additive count
        tensor, so folding per-chunk cross_counts yields bit-identical
        tables to the whole-file pass at O(chunk) host RSS."""
        self = cls()
        for ds in chunks:
            self.add(ds)
        if self.fields is None:
            raise ValueError("no input chunks")
        self.finalize()
        return self

    def add(self, ds: Dataset) -> None:
        """Fold one chunk's contingency counts into the running tables.
        Data-discovered categorical vocabularies may extend between chunks
        (the shared-schema contract of CsvBlockReader); accumulated tables
        zero-pad along the grown bin axes.

        All F feature-class tables and both F(F-1)/2 pair-table families
        come out of THREE keyed segment_sums per chunk (bin axes padded to
        the chunk's max bin count) — not one dispatch per table, so the
        streaming path pays three dispatch latencies per chunk, not F^2."""
        if self.fields is None:
            self.fields = ds.encodable_feature_fields()
            self.k = ds.schema.num_classes()
            F = len(self.fields)
            self.bins = [0] * F
            self._fc = [np.zeros((0, self.k), np.float64) for _ in range(F)]
        codes, bins = ds.feature_codes(self.fields)
        F = len(self.fields)
        self.bins = [max(a, b) for a, b in zip(self.bins, bins)]
        bmax = max(bins) if bins else 1
        fused_keys = (F * (F - 1) // 2) * bmax * bmax * self.k
        if fused_keys < _FUSED_KEYSPACE_LIMIT:
            # device segment_sums on accelerators; vectorized bincount on
            # CPU hosts (XLA:CPU scatter is serial — see the host fn).
            # Integer counts: both produce bit-identical tables.
            if jax.default_backend() == "cpu":
                kernel, codes_a, y_a = (_mi_chunk_counts_host, codes,
                                        ds.labels())
            else:
                kernel = _mi_chunk_counts
                codes_a, y_a = jnp.asarray(codes), jnp.asarray(ds.labels())
            fc, pair, pairc = (np.asarray(a, np.float64) for a in
                               kernel(codes_a, y_a, bmax, self.k, F))
            p = 0
            for i in range(F):
                self._fc[i] = _padded_add(self._fc[i], fc[i, :bins[i]])
                for j in range(i + 1, F):
                    bi, bj = bins[i], bins[j]
                    self._pair[(i, j)] = _padded_add(
                        self._pair.get((i, j)), pair[p, :bi, :bj])
                    self._pairc[(i, j)] = _padded_add(
                        self._pairc.get((i, j)), pairc[p, :bi, :bj])
                    p += 1
        else:
            # fused int32 keys would wrap (many features x huge bin
            # counts): per-pair cross_counts, each in its own keyspace
            codes_d = jnp.asarray(codes)
            y = jnp.asarray(ds.labels())
            for f in range(F):
                self._fc[f] = _padded_add(self._fc[f], np.asarray(
                    cross_count(codes_d[:, f], y, bins[f], self.k),
                    np.float64))
            for i in range(F):
                for j in range(i + 1, F):
                    bi, bj = bins[i], bins[j]
                    self._pair[(i, j)] = _padded_add(
                        self._pair.get((i, j)), np.asarray(
                            cross_count(codes_d[:, i], codes_d[:, j],
                                        bi, bj), np.float64))
                    comb = codes_d[:, i] * bj + codes_d[:, j]
                    self._pairc[(i, j)] = _padded_add(
                        self._pairc.get((i, j)), np.asarray(
                            cross_count(comb, y, bi * bj, self.k),
                            np.float64).reshape(bi, bj, self.k))
        self.n += len(ds)

    def merge(self, other: "MutualInformationAnalyzer"
              ) -> "MutualInformationAnalyzer":
        """Fold another analyzer's count tables into this one — the
        NaiveBayesModel.merge algebra for MI: every table is an additive
        integer-count tensor, so ``merge(add(A), add(B))`` equals
        ``add(A ++ B)`` exactly (the shard-merge contract graftlint
        --merge proves mechanically). Both sides must be un-finalized
        partial fits over the same feature set; an empty `other` (no
        chunks seen) merges as a no-op, and an empty `self` adopts
        `other`'s state. Grown data-discovered vocabularies zero-pad
        along the bin axes, exactly like chunked add()."""
        if other.fields is None:
            return self
        if self.fields is None:
            self.fields = other.fields
            self.k = other.k
            self.bins = [0] * len(other.fields)
            self._fc = [np.zeros((0, self.k), np.float64)
                        for _ in other.fields]
        if self.k != other.k or [f.ordinal for f in self.fields] != \
                [f.ordinal for f in other.fields]:
            raise ValueError(
                "cannot merge MI analyzers over different feature sets "
                "or class counts")
        self.bins = [max(a, b) for a, b in zip(self.bins, other.bins)]
        for i in range(len(self.fields)):
            self._fc[i] = _padded_add(self._fc[i], other._fc[i])
        for key, tbl in other._pair.items():
            self._pair[key] = _padded_add(self._pair.get(key), tbl)
        for key, tbl in other._pairc.items():
            self._pairc[key] = _padded_add(self._pairc.get(key), tbl)
        self.n += other.n
        return self

    def finalize(self) -> None:
        """Derive all MI statistics from the accumulated count tables."""
        F = len(self.bins)
        self.feature_class_mi = np.zeros(F)
        self.pair_mi = np.zeros((F, F))
        self.pair_class_mi = np.zeros((F, F))
        self.pair_class_entropy = np.zeros((F, F))
        for f in range(F):
            self.feature_class_mi[f] = float(
                mutual_information(jnp.asarray(self._fc[f])))
        for (i, j), joint_ij in self._pair.items():
            mi_ij = float(mutual_information(jnp.asarray(joint_ij)))
            self.pair_mi[i, j] = self.pair_mi[j, i] = mi_ij
        for (i, j), joint_ijc in self._pairc.items():
            flat = jnp.asarray(joint_ijc.reshape(-1, self.k))
            mic = float(mutual_information(flat))
            self.pair_class_mi[i, j] = self.pair_class_mi[j, i] = mic
            h = float(entropy(flat.reshape(-1), axis=-1))
            self.pair_class_entropy[i, j] = self.pair_class_entropy[j, i] = h

    # ------------------------------------------------------------- scores
    def _ordinals(self) -> List[int]:
        return [f.ordinal for f in self.fields]

    def mim(self) -> List[Tuple[int, float]]:
        """Max relevance: features sorted by I(Xf; C) descending."""
        order = np.argsort(-self.feature_class_mi)
        ords = self._ordinals()
        return [(ords[i], float(self.feature_class_mi[i])) for i in order]

    def mifs(self, redundancy_factor: float = 1.0) -> List[Tuple[int, float]]:
        """Greedy: score = I(Xf;C) - beta * sum_{s in selected} I(Xf;Xs)."""
        F = len(self.bins)
        selected: List[int] = []
        out = []
        while len(selected) < F:
            best, best_score = -1, -np.inf
            for f in range(F):
                if f in selected:
                    continue
                red = sum(self.pair_mi[f, s] for s in selected)
                score = self.feature_class_mi[f] - redundancy_factor * red
                if score > best_score:
                    best, best_score = f, score
            selected.append(best)
            out.append((self._ordinals()[best], float(best_score)))
        return out

    def _jmi_helper(self, joint: bool) -> List[Tuple[int, float]]:
        F = len(self.bins)
        first = int(np.argmax(self.feature_class_mi))
        selected = [first]
        out = [(self._ordinals()[first], float(self.feature_class_mi[first]))]
        while len(selected) < F:
            best, best_score = -1, -np.inf
            for f in range(F):
                if f in selected:
                    continue
                if joint:
                    s_sum = sum(self.pair_class_mi[f, s] for s in selected)
                else:
                    s_sum = sum(
                        self.pair_class_mi[f, s]
                        / max(self.pair_class_entropy[f, s], _EPS)
                        for s in selected
                    )
                if s_sum > best_score:
                    best, best_score = f, s_sum
            selected.append(best)
            out.append((self._ordinals()[best], float(best_score)))
        return out

    def jmi(self) -> List[Tuple[int, float]]:
        """Joint mutual information selection."""
        return self._jmi_helper(True)

    def disr(self) -> List[Tuple[int, float]]:
        """Double-input symmetric relevance (JMI normalized by pair entropy)."""
        return self._jmi_helper(False)

    def mrmr(self) -> List[Tuple[int, float]]:
        """Greedy: score = I(Xf;C) - mean_{s in selected} I(Xf;Xs)."""
        F = len(self.bins)
        selected: List[int] = []
        out = []
        while len(selected) < F:
            best, best_score = -1, -np.inf
            for f in range(F):
                if f in selected:
                    continue
                red = sum(self.pair_mi[f, s] for s in selected)
                score = (
                    self.feature_class_mi[f] - red / len(selected)
                    if selected else self.feature_class_mi[f]
                )
                if score > best_score:
                    best, best_score = f, score
            selected.append(best)
            out.append((self._ordinals()[best], float(best_score)))
        return out

    def score(self, algorithm: str, redundancy_factor: float = 1.0):
        """Dispatch by the reference's mut.* algorithm names."""
        return {
            "mutual.info.maximization": self.mim,
            "mutual.info.selection": lambda: self.mifs(redundancy_factor),
            "joint.mutual.info": self.jmi,
            "double.input.symmetric.relevance": self.disr,
            "min.redundancy.max.relevance": self.mrmr,
        }[algorithm]()


# ---------------------------------------------------------------------------
# candidate-split class partition stats
# ---------------------------------------------------------------------------
class ClassPartitionGenerator:
    """Candidate-split class-histogram stats — the older two-job tree flow's
    first stage (explore/ClassPartitionGenerator.java:61, cpg.* keys).

    For every candidate split of the requested attributes, one device
    segment_sum produces the [segment, class] histogram; the split stat is
    computed per cpg.split.algorithm: `entropy` / `giniIndex` (weighted
    child info content, lower = better) or `hellingerDistance`
    (AttributeSplitStat.java:228-283, higher = better, binary class only).
    """

    def __init__(self, ds: Dataset, attributes: Optional[Sequence[int]] = None,
                 algorithm: str = "giniIndex", cat_partition_cap: int = 128):
        from avenir_tpu.models.tree import enumerate_splits

        self.ds = ds
        self.algorithm = algorithm
        splits = enumerate_splits(ds.schema, cat_partition_cap)
        if attributes is not None:
            attrs = set(attributes)
            splits = [s for s in splits if s.attribute in attrs]
        self.splits = splits
        self.k = ds.schema.num_classes()
        self.histograms = self._histograms()

    def _histograms(self) -> List[np.ndarray]:
        """Per split: [n_segments, k] class counts — the tree level
        histogram kernel with a single root leaf."""
        from avenir_tpu.models.tree import (_level_histogram, segment_matrix,
                                            to_lines)

        if not self.splits:
            return []
        n = len(self.ds)
        smax = max(s.n_segments for s in self.splits)
        labels = to_lines(self.ds.labels())
        hists = np.asarray(_level_histogram(
            jnp.zeros(labels.shape, jnp.int32),
            segment_matrix(self.splits, self.ds),
            jnp.asarray(labels), jnp.asarray(to_lines(np.ones(n, np.int32))),
            1, smax, self.k,
        ))[0].astype(np.float64)                             # [NS, smax, k]
        return [hists[i, : s.n_segments] for i, s in enumerate(self.splits)]

    def split_stats(self) -> List[Tuple[object, float]]:
        """(CandidateSplit, stat) per candidate, computed per algorithm."""
        out = []
        for s, h in zip(self.splits, self.histograms):
            if self.algorithm == "hellingerDistance":
                if self.k != 2:
                    raise ValueError("Hellinger distance algorithm is only "
                                     "valid for binary valued class attributes")
                tot = np.maximum(h.sum(axis=0), _EPS)        # per-class totals
                d = np.sqrt(h[:, 0] / tot[0]) - np.sqrt(h[:, 1] / tot[1])
                stat = float(np.sqrt((d * d).sum()))
            else:
                stat = float(weighted_split_score(jnp.asarray(h), self.algorithm))
            out.append((s, stat))
        return out

    def best_split(self):
        """(CandidateSplit, stat): max stat for Hellinger, min info content
        for entropy/gini."""
        stats = self.split_stats()
        pick = max if self.algorithm == "hellingerDistance" else min
        return pick(stats, key=lambda t: t[1])


# ---------------------------------------------------------------------------
# correlations
# ---------------------------------------------------------------------------


def contingency(ds: Dataset, fld: FeatureField) -> np.ndarray:
    """[Bf, K] feature-value x class count table (one one-hot matmul)."""
    codes, _ = ds.feature_codes([fld])
    return np.asarray(cross_count(
        jnp.asarray(codes[:, 0]), jnp.asarray(ds.labels()),
        fld.num_bins(), ds.schema.num_classes(),
    ))


def cramer_index(table: np.ndarray) -> float:
    """Cramér index V^2 = chi2 / (n * min(r-1, c-1))
    (CramerCorrelation.java via chombo ContingencyMatrix)."""
    n = table.sum()
    if n == 0:
        return 0.0
    row = table.sum(axis=1, keepdims=True)
    col = table.sum(axis=0, keepdims=True)
    expected = row @ col / n
    chi2 = float(np.where(expected > 0,
                          (table - expected) ** 2 / np.maximum(expected, _EPS),
                          0.0).sum())
    r, c = table.shape
    denom = n * max(min(r - 1, c - 1), 1)
    return chi2 / denom


class ContingencyAccumulator:
    """Streaming per-field feature-value x class contingency tables.

    The whole correlation family (Cramér, categorical, heterogeneity
    reduction) is a function of these [B, K] tables, and the tables are
    additive over records — the reference's mapper/combiner/reducer count
    algebra (CramerCorrelation.java:54) at chunk granularity. The bin axis
    grows in place as data-discovered vocabularies extend between chunks."""

    def __init__(self):
        self.fields: Optional[List[FeatureField]] = None
        self.tables: Dict[int, np.ndarray] = {}      # ordinal -> [B, K]
        self.class_counts: Optional[np.ndarray] = None
        self.k = 0
        self.n = 0

    def add(self, ds: Dataset) -> None:
        if self.fields is None:
            self.fields = [f for f in ds.schema.feature_fields
                           if f.num_bins() > 0]
            self.k = ds.schema.num_classes()
            self.class_counts = np.zeros(self.k, np.float64)
        y = ds.labels()
        self.class_counts += np.bincount(y, minlength=self.k)
        if self.fields:
            codes, bins = ds.feature_codes(self.fields)
            codes_d = jnp.asarray(codes)
            yd = jnp.asarray(y)
            for i, f in enumerate(self.fields):
                tab = np.asarray(
                    cross_count(codes_d[:, i], yd, bins[i], self.k),
                    np.float64)
                self.tables[f.ordinal] = _padded_add(
                    self.tables.get(f.ordinal), tab)
        self.n += len(ds)

    def cramer(self) -> Dict[int, float]:
        return {o: cramer_index(t) for o, t in sorted(self.tables.items())}

    def heterogeneity(self, algo: str = "entropy") -> Dict[int, float]:
        imp_fn = bits_entropy if algo == "entropy" else gini
        base = float(np.asarray(imp_fn(jnp.asarray(self.class_counts))))
        out = {}
        for o, tab in sorted(self.tables.items()):
            seg_tot = tab.sum(axis=1)
            seg_imp = np.asarray(imp_fn(jnp.asarray(tab), axis=-1))
            cond = float((seg_tot / max(seg_tot.sum(), _EPS) * seg_imp).sum())
            out[o] = (base - cond) / max(base, _EPS)
        return out


class NumericMomentAccumulator:
    """Streaming Pearson moments (n, sum, cross-products) over the numeric
    features + numeric-coded class (NumericalCorrelation.java:48). The
    correlation matrix from raw moments equals np.corrcoef's (the
    normalization factor cancels in the ratio)."""

    def __init__(self):
        self.n = 0
        self.s: Optional[np.ndarray] = None
        self.ss: Optional[np.ndarray] = None

    def add(self, ds: Dataset) -> None:
        x = ds.feature_matrix()
        y = ds.labels().astype(np.float32)[:, None]
        m = np.concatenate([x, y], axis=1).astype(np.float64)
        if self.s is None:
            d = m.shape[1]
            self.s = np.zeros(d, np.float64)
            self.ss = np.zeros((d, d), np.float64)
        self.n += m.shape[0]
        self.s += m.sum(axis=0)
        self.ss += m.T @ m

    def correlation(self) -> np.ndarray:
        mean = self.s / max(self.n, 1)
        cov = self.ss / max(self.n, 1) - np.outer(mean, mean)
        sd = np.sqrt(np.clip(np.diag(cov), _EPS, None))
        return cov / np.outer(sd, sd)


def cramer_correlation(ds: Dataset) -> Dict[int, float]:
    """Per-categorical-feature Cramér index against the class attribute."""
    acc = ContingencyAccumulator()
    acc.add(ds)
    return acc.cramer()


def heterogeneity_reduction(ds: Dataset, algo: str = "entropy") -> Dict[int, float]:
    """Proportional impurity reduction of the class by each feature
    (HeterogeneityReductionCorrelation.java:38):
    (imp(C) - sum_b p(b) imp(C|b)) / imp(C)."""
    acc = ContingencyAccumulator()
    acc.add(ds)
    return acc.heterogeneity(algo)


def numerical_correlation(ds: Dataset) -> np.ndarray:
    """Pearson correlation matrix over numeric features + numeric-coded
    class, via a single moment pass (NumericalCorrelation.java:48)."""
    acc = NumericMomentAccumulator()
    acc.add(ds)
    return acc.correlation()


# ---------------------------------------------------------------------------
# Relief feature relevance
# ---------------------------------------------------------------------------


def relief_relevance(
    ds: Dataset,
    sample_size: Optional[int] = None,
    seed: int = 0,
    block: int = 8192,
    query_block: int = 8192,
) -> Dict[int, float]:
    """Relief: w_f += diff_f(x, nearest miss) - diff_f(x, nearest hit),
    averaged over sampled records (ReliefFeatureRelevance.java:49).

    Device-scale: nearest hit/miss come from per-class blocked streaming
    top-k (ops.distance.blocked_topk_neighbors) with query chunking, so
    peak memory is O(query_block x block) — never the [m, m] diff
    matrices. The per-attribute-averaged manhattan metric of the search
    is relief's own mean of range-normalized diffs, so hit/miss selection
    is unchanged; the final per-feature weights evaluate those diffs only
    at the selected (record, hit/miss) pairs. Ranges use the schema's
    min/max with a data-derived fallback, as the reference's metric."""
    from avenir_tpu.ops.distance import blocked_topk_neighbors, pad_train

    n = len(ds)
    rng = np.random.default_rng(seed)
    idx = (np.arange(n) if sample_size is None or sample_size >= n
           else rng.choice(n, sample_size, replace=False))
    sub = ds.take(idx)
    y = sub.labels()
    m = len(sub)
    k_classes = ds.schema.num_classes()

    num_fields = [f for f in ds.schema.feature_fields if f.is_numeric]
    cat_fields = [f for f in ds.schema.feature_fields if f.is_categorical]
    num_cols, ranges = [], []
    for f in num_fields:
        col = sub.column(f.ordinal).astype(np.float32)
        rngf = (f.max - f.min) if f.max is not None and f.min is not None else (
            float(col.max() - col.min()) or 1.0)
        num_cols.append(col)
        ranges.append(max(rngf, _EPS))
    x_num = (np.stack(num_cols, axis=1) if num_cols
             else np.zeros((m, 0), np.float32))
    ranges_arr = np.asarray(ranges, np.float32)
    if cat_fields:
        x_cat = np.stack([sub.column(f.ordinal).astype(np.int32)
                          for f in cat_fields], axis=1)
        bins = tuple(len(f.cardinality) for f in cat_fields)
    else:
        x_cat, bins = None, None

    # nearest neighbor of every record within each class (self excluded)
    best_d = np.full((m, k_classes), np.inf, np.float32)
    best_i = np.zeros((m, k_classes), np.int64)
    q_num_j = jnp.asarray(x_num) if x_num.shape[1] else None
    q_cat_j = jnp.asarray(x_cat) if x_cat is not None else None
    rng_j = jnp.asarray(ranges_arr) if ranges_arr.size else None
    for ki in range(k_classes):
        rows_c = np.flatnonzero(y == ki)
        if len(rows_c) == 0:
            continue
        blk = min(block, len(rows_c))
        t_num, t_cat, n_valid = pad_train(
            x_num[rows_c] if x_num.shape[1] else None,
            x_cat[rows_c] if x_cat is not None else None, blk)
        kk = min(2, len(rows_c))
        t_num_j = jnp.asarray(t_num) if t_num is not None else None
        t_cat_j = jnp.asarray(t_cat) if t_cat is not None else None
        for qs in range(0, m, query_block):
            qe = min(qs + query_block, m)
            dist, nidx = blocked_topk_neighbors(
                q_num_j[qs:qe] if q_num_j is not None else None,
                t_num_j,
                q_cat_j[qs:qe] if q_cat_j is not None else None,
                t_cat_j,
                cat_bins=bins, num_ranges=rng_j, k=kk, block=blk,
                metric="manhattan", n_valid=n_valid)
            dist, nidx = np.asarray(dist), np.asarray(nidx)
            in_c = y[qs:qe] == ki
            # in-class queries find themselves first: take the runner-up
            sel = np.where(in_c, kk - 1, 0)
            r = np.arange(qe - qs, dtype=np.int32)
            d = dist[r, sel]
            j = nidx[r, sel]
            if kk == 1:        # a singleton class has no non-self hit
                d = np.where(in_c, np.inf, d)
            best_d[qs:qe, ki] = d
            best_i[qs:qe, ki] = rows_c[np.clip(j, 0, len(rows_c) - 1)]

    rows = np.arange(m)
    hit_i = best_i[rows, y]
    hit_ok = np.isfinite(best_d[rows, y])
    miss_view = best_d.copy()
    miss_view[rows, y] = np.inf
    miss_cls = miss_view.argmin(axis=1)
    miss_i = best_i[rows, miss_cls]
    miss_ok = np.isfinite(miss_view[rows, miss_cls])
    valid = hit_ok & miss_ok
    if not valid.any():
        return {f.ordinal: 0.0 for f in num_fields + cat_fields}

    weights = {}
    for fi, f in enumerate(num_fields):
        col = x_num[:, fi]
        d_hit = np.abs(col - col[hit_i]) / ranges_arr[fi]
        d_miss = np.abs(col - col[miss_i]) / ranges_arr[fi]
        weights[f.ordinal] = float((d_miss - d_hit)[valid].mean())
    for fi, f in enumerate(cat_fields):
        col = x_cat[:, fi]
        d_hit = (col != col[hit_i]).astype(np.float32)
        d_miss = (col != col[miss_i]).astype(np.float32)
        weights[f.ordinal] = float((d_miss - d_hit)[valid].mean())
    return weights


# ---------------------------------------------------------------------------
# class affinity + supervised encoding
# ---------------------------------------------------------------------------


def class_affinity_from_table(tab: np.ndarray, fld: FeatureField,
                              class_values: Sequence[str], top_n: int = 3
                              ) -> Dict[str, List[Tuple[str, float]]]:
    """class_affinity from an accumulated [B, K] contingency table —
    the streaming form (tables fold additively per chunk)."""
    cls_tot = tab.sum(axis=0)
    out = {}
    for ki, cv in enumerate(class_values):
        p = tab[:, ki] / max(cls_tot[ki], _EPS)
        order = np.argsort(-p)[:top_n]
        out[cv] = [(fld.cardinality[b], float(p[b])) for b in order
                   if b < len(fld.cardinality)]
    return out


def class_affinity(ds: Dataset, fld: FeatureField, top_n: int = 3
                   ) -> Dict[str, List[Tuple[str, float]]]:
    """Per class: top-n categorical values by P(value | class)
    (CategoricalClassAffinity.java:51)."""
    return class_affinity_from_table(contingency(ds, fld), fld,
                                     ds.schema.class_values(), top_n)


def supervised_encoding_from_table(
    tab: np.ndarray,
    fld: FeatureField,
    classes: Sequence[str],
    strategy: str = "supervisedRatio",
    pos_class: Optional[str] = None,
) -> Dict[str, float]:
    """supervised_encoding from an accumulated [B, K] contingency table —
    the streaming form."""
    pi = classes.index(pos_class) if pos_class else 1
    pos = tab[:, pi]
    neg = tab.sum(axis=1) - pos
    total_pos = max(pos.sum(), _EPS)
    total_neg = max(neg.sum(), _EPS)
    out = {}
    for b, value in enumerate(fld.cardinality[:tab.shape[0]]):
        if strategy == "weightOfEvidence":
            num = max(pos[b], 0.5) / total_pos        # 0.5 = continuity corr.
            den = max(neg[b], 0.5) / total_neg
            out[value] = math.log(num / den)
        else:
            out[value] = float(pos[b] / max(pos[b] + neg[b], _EPS))
    return out


def supervised_encoding(
    ds: Dataset,
    fld: FeatureField,
    strategy: str = "supervisedRatio",
    pos_class: Optional[str] = None,
) -> Dict[str, float]:
    """Categorical value -> continuous code
    (CategoricalContinuousEncoding.java:47, coe.encoding.strategy):
      supervisedRatio: count(value, pos) / count(value)
      weightOfEvidence: ln( (count(value,pos)/total_pos) /
                            (count(value,neg)/total_neg) )
    """
    return supervised_encoding_from_table(
        contingency(ds, fld), fld, ds.schema.class_values(),
        strategy, pos_class)


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------


def undersample_balance(ds: Dataset, seed: int = 0) -> Dataset:
    """Undersample majority classes to the minority count
    (UnderSamplingBalancer.java:45)."""
    y = ds.labels()
    rng = np.random.default_rng(seed)
    counts = np.bincount(y, minlength=ds.schema.num_classes())
    target = counts[counts > 0].min()
    keep = []
    for c in range(len(counts)):
        rows = np.flatnonzero(y == c)
        if len(rows) > target:
            rows = rng.choice(rows, target, replace=False)
        keep.append(rows)
    keep = np.sort(np.concatenate(keep))
    return ds.take(keep)


def bagging_sample(ds: Dataset, rate: float = 1.0, seed: int = 0) -> Dataset:
    """Bootstrap sample (BaggingSampler.java:47)."""
    rng = np.random.default_rng(seed)
    n = len(ds)
    idx = rng.integers(0, n, int(n * rate))
    return ds.take(idx)


# ---------------------------------------------------------------------------
# top matches by class + rule evaluation
# ---------------------------------------------------------------------------


def top_matches_by_class(ds: Dataset, k: int = 3, block: int = 4096,
                         query_block: int = 16384
                         ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Per class: k nearest same-class neighbors for each record of that
    class (TopMatchesByClass.java:47). Returns class -> (dist [m, k],
    global dataset row idx [m, k]); row r of the pair is the class's r-th
    record in dataset order (np.flatnonzero(labels == class)).

    Queries stream in `query_block` chunks against the blocked index, so
    peak memory is O(query_block x block) however large the class."""
    from avenir_tpu.models.knn import NeighborIndex

    y = ds.labels()
    out = {}
    for ki, cv in enumerate(ds.schema.class_values()):
        rows = np.flatnonzero(y == ki)
        if len(rows) < 2:
            continue
        sub = ds.take(rows)
        index = NeighborIndex(sub, k=min(k + 1, len(rows)), block=block)
        dists, idxs = [], []
        for qs in range(0, len(rows), query_block):
            d, i = index.neighbors(
                sub.take(np.arange(qs, min(qs + query_block, len(rows)),
                                   dtype=np.int32)))
            dists.append(np.asarray(d))
            idxs.append(np.asarray(i))
        dist = np.concatenate(dists)
        idx = np.concatenate(idxs)
        # first neighbor is self (distance 0); drop it
        out[cv] = (dist[:, 1:], rows[idx[:, 1:]])
    return out


@dataclass
class Rule:
    """condition => consequence, both conjunctions of simple predicates
    "attr op value" with op in (eq, ne, gt, ge, lt, le, in)
    (RuleEvaluator.java:48, util/RuleExpression.java)."""

    condition: List[str]
    consequence: List[str]

    @staticmethod
    def _eval_one(ds: Dataset, expr: str) -> np.ndarray:
        toks = expr.strip().split(None, 2)
        attr, op, val = int(toks[0]), toks[1], toks[2]
        fld = ds.schema.field_by_ordinal(attr)
        col = ds.column(attr)
        if fld.is_categorical:
            index = fld.cardinality_index()
            if op == "in":
                codes = [index[v] for v in val.split(":") if v in index]
                return np.isin(col.astype(np.int64), codes)
            code = index[val]
            m = col.astype(np.int64) == code
            return m if op == "eq" else ~m
        x = col.astype(np.float64)
        v = float(val)
        return {
            "eq": x == v, "ne": x != v, "gt": x > v, "ge": x >= v,
            "lt": x < v, "le": x <= v,
        }[op]

    def counts(self, ds: Dataset) -> Tuple[int, int, int]:
        """(rows, conditionCount, bothCount) for one chunk — additive, so
        rule evaluation streams like every other counting job."""
        cond = np.ones(len(ds), bool)
        for e in self.condition:
            cond &= self._eval_one(ds, e)
        cons = np.ones(len(ds), bool)
        for e in self.consequence:
            cons &= self._eval_one(ds, e)
        return len(ds), int(cond.sum()), int((cond & cons).sum())

    @staticmethod
    def finalize(n: int, cond: int, both: int) -> Dict[str, float]:
        return {"support": float(both / n if n else 0.0),
                "confidence": float(both / max(cond, 1)),
                "conditionCount": cond, "bothCount": both}

    def evaluate(self, ds: Dataset) -> Dict[str, float]:
        return self.finalize(*self.counts(ds))
