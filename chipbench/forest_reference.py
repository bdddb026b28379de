"""The plain reference of a random-forest build (`randomForest`, and
`decTree` as a forest of one tree that weighs every row 1): candidate
splits, bootstrap weights, level-wise growth and the exact statistics of a
path, in plain numpy over whole columns. Imports nothing of the program
and touches no device.

Rows are `codes` [n, d] (per feature field, by ordinal: a categorical
field's index into its `cardinality`, an `int` field's number) and `y` [n]
(index into the classes). Rows that agree in every feature and in the
class may be handed in once, with `weights` [T, m] the sum of theirs: every
number here is a sum over rows, so the answers are the same (`compact`).

**Candidate splits** (upstream SplitManager): a numeric field's boundaries
stand at `splitScanInterval` steps inside (min, max), and a split keeps 1
to `maxSplit` - 1 of them, fewer first, in the order `itertools.combinations`
gives; a categorical field's values are partitioned into 2 to `maxSplit`
groups, every set partition once, in the order of their restricted-growth
strings. `maxSplit` is 2 where the field states none.

**The sampling rule** (the job's contract: the forest is a function of the
input file and the seed). One `np.random.default_rng(seed)` serves all
trees in tree order: under `withReplace` tree t's sample is
`rng.integers(0, n, n)` and a row weighs how often it was drawn; under
`withoutReplace` it weighs `rng.random(n) < rate`; otherwise 1. Tree t
picks attributes with `np.random.default_rng(seed + t)`: level by level,
each open leaf in leaf order draws once (`randomNotUsedYet`: `choice` of
floor(sqrt(a)), at least one, of the a attributes its path has not used,
without replacement; no draw where none is left), whether or not it can
then be split. Children take the leaf numbers after all leaves so far, a
split parent's segments in order.

**A split is chosen** among the candidate splits of the drawn attributes
as the first of least weighted impurity: sum over segments of (segment
weight x impurity of the segment's class counts) over the leaf's weight,
in float64 from integer counts.
"""

from __future__ import annotations

import itertools
import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_OPS = {"lt": np.less, "ge": np.greater_equal}


# ------------------------------------------------------------------ schema
def feature_fields(schema: Dict) -> List[Dict]:
    """The fields flagged `feature`, by ordinal, categorical ones too."""
    return sorted((f for f in schema["fields"] if f.get("feature")),
                  key=lambda f: f["ordinal"])


def _set_partitions(n_items: int, most: int) -> List[List[int]]:
    """Group numbers of `n_items` items for every partition into 2 to
    `most` groups, as restricted-growth strings in lexicographic order."""
    out: List[List[int]] = []

    def grow(prefix: List[int], groups: int) -> None:
        if len(prefix) == n_items:
            if 2 <= groups <= most:
                out.append(list(prefix))
            return
        for g in range(min(groups + 1, most)):
            grow(prefix + [g], max(groups, g + 1))

    grow([], 0)
    return out


def candidate_splits(schema: Dict) -> List[Dict]:
    """Every candidate split of every feature field, in stable order. A
    split: `attribute` (the field's ordinal), `column` (its place among
    the feature fields), `segments`, `predicates` (one a segment, as
    `predicate_key` reads them from a model file) and either `bounds` or
    `group_of` (value index -> segment)."""
    splits: List[Dict] = []
    for col, f in enumerate(feature_fields(schema)):
        most = int(f.get("maxSplit") or 2)
        o = f["ordinal"]
        if f["dataType"] == "categorical":
            names = f["cardinality"]
            for assign in _set_partitions(len(names), min(most, len(names))):
                groups = max(assign) + 1
                preds = [("in", o, frozenset(
                    v for v, g in zip(names, assign) if g == s))
                    for s in range(groups)]
                splits.append({"attribute": o, "column": col,
                               "segments": groups, "predicates": preds,
                               "group_of": np.asarray(assign, np.int64)})
        else:
            step = f.get("splitScanInterval") or f.get("bucketWidth")
            points, p = [], f["min"] + step
            while p < f["max"] - 1e-9:
                points.append(p)
                p += step
            for nseg in range(2, most + 1):
                for bounds in itertools.combinations(points, nseg - 1):
                    preds = []
                    for s in range(nseg):
                        if s == 0:
                            preds.append(("lt", o, bounds[0], None))
                        elif s == nseg - 1:
                            preds.append(("ge", o, bounds[-1], None))
                        else:
                            preds.append(("ge", o, bounds[s - 1], bounds[s]))
                    splits.append({"attribute": o, "column": col,
                                   "segments": nseg, "predicates": preds,
                                   "bounds": np.asarray(bounds, np.float64)})
    return splits


def segment_ids(split: Dict, codes: np.ndarray) -> np.ndarray:
    """[n] the segment of every row under one split."""
    col = codes[:, split["column"]]
    if "group_of" in split:
        return split["group_of"][col]
    return np.searchsorted(split["bounds"], col, side="right")


def predicate_key(pred: Dict) -> Tuple:
    """One predicate of a model file in the form `candidate_splits` lists
    them; raises KeyError or TypeError on one it cannot read."""
    if pred["operator"] == "in":
        return ("in", int(pred["attribute"]),
                frozenset(pred["categoricalValues"]))
    other = pred.get("otherBoundInt")
    return (pred["operator"], int(pred["attribute"]), int(pred["valueInt"]),
            None if other is None else int(other))


def rows_of(preds: Sequence[Tuple], schema: Dict, codes: np.ndarray
            ) -> np.ndarray:
    """[n] bool: the rows that satisfy every predicate of a path."""
    fields = feature_fields(schema)
    at = {f["ordinal"]: j for j, f in enumerate(fields)}
    keep = np.ones(len(codes), bool)
    for pred in preds:
        j = at[pred[1]]
        if pred[0] == "in":
            names = fields[j]["cardinality"]
            keep &= np.isin(codes[:, j], [names.index(v) for v in pred[2]])
        else:
            keep &= _OPS[pred[0]](codes[:, j], pred[2])
            if pred[3] is not None:
                keep &= codes[:, j] < pred[3]
    return keep


# ----------------------------------------------------------------- sampling
def bootstrap_weights(seed: int, n: int, trees: int, sampling: str,
                      rate: float = 0.7, threads: int = 3) -> np.ndarray:
    """[T, n] int64 by the sampling rule. The draws are made in tree
    order on this thread; counting a finished draw may run beside the
    next draw."""
    rng = np.random.default_rng(seed)
    ws = np.empty((trees, n), np.int64)

    def count(t, idx):
        ws[t] = np.bincount(idx, minlength=n)

    with ThreadPoolExecutor(threads) as pool:
        jobs = []
        for t in range(trees):
            if sampling == "withReplace":
                jobs.append(pool.submit(count, t, rng.integers(0, n, n)))
            elif sampling == "withoutReplace":
                ws[t] = rng.random(n) < rate
            else:
                ws[t] = 1
        for j in jobs:
            j.result()
    return ws


def compact(codes: np.ndarray, y: np.ndarray, radix: Sequence[int],
            classes: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(cell [n], codes [G, d], y [G]): every row's number among the G =
    prod(radix) x classes distinct (features, class) rows there can be,
    and those rows themselves. Summing a tree's weights by `cell`
    (`sum_by_cell`) gives the weights of the G rows: the same forest
    statistics from G rows as from n."""
    cell = np.zeros(len(codes), np.int64)
    for j, r in enumerate(radix):
        cell = cell * r + codes[:, j]
    cell = cell * classes + y
    g = int(np.prod(radix)) * classes
    rest = np.arange(g)
    rest, gy = np.divmod(rest, classes)
    cols = []
    for r in reversed(radix):
        rest, c = np.divmod(rest, r)
        cols.append(c)
    return cell, np.stack(cols[::-1], axis=1), gy


def sum_by_cell(cell: np.ndarray, weights: Optional[np.ndarray], cells: int
                ) -> np.ndarray:
    """[G] int64: `weights` (1 where none) summed over the rows of each
    cell. float64 adds whole numbers exactly below 2^53."""
    return np.rint(np.bincount(cell, weights=weights, minlength=cells)
                   ).astype(np.int64)


# ------------------------------------------------------------------ counts
def class_counts(y: np.ndarray, w: np.ndarray, keep: np.ndarray, classes: int
                 ) -> np.ndarray:
    """[K] int64: the weighted class counts of the rows `keep`."""
    return np.rint(np.bincount(y[keep], weights=w[keep], minlength=classes)
                   ).astype(np.int64)


def split_counts(split: Dict, codes, y, w, keep, classes: int) -> np.ndarray:
    """[S, K] int64: the weighted class histogram of one candidate split
    over the rows `keep`."""
    key = segment_ids(split, codes[keep]) * classes + y[keep]
    flat = np.bincount(key, weights=w[keep],
                       minlength=split["segments"] * classes)
    return np.rint(flat).astype(np.int64).reshape(split["segments"], classes)


def impurity(counts: np.ndarray, algorithm: str) -> float:
    """giniIndex or entropy (bits) of class counts, in float64."""
    tot = counts.sum()
    if tot <= 0:
        return 0.0
    p = counts.astype(np.float64) / tot
    if algorithm in ("entropy", "infoGain"):
        nz = p[p > 0]
        return float(-(nz * np.log(nz)).sum() / math.log(2.0))
    return float(1.0 - (p * p).sum())


def weighted_impurity(counts: np.ndarray, algorithm: str) -> float:
    """Of a split's [S, K] counts: the segments' impurities weighted by
    their share of the node."""
    tot = counts.sum()
    if tot <= 0:
        return 0.0
    return sum(float(seg.sum()) * impurity(seg, algorithm)
               for seg in counts) / float(tot)


# ------------------------------------------------------------------ growth
def job_semantics(properties: Dict[str, str], forest: bool = True) -> Dict:
    """What the job has to compute, from the `dtb.*` properties the
    program reads, with the job's own defaults."""
    def get(key, default):
        return properties.get("dtb." + key, default)
    return {"trees": int(get("num.trees", "10")) if forest else 1,
            "sampling": get("sub.sampling.strategy", "withReplace")
            if forest else "none",
            "rate": float(get("sub.sampling.rate", "0.7")),
            "algorithm": get("split.algorithm", "entropy"),
            "max_depth": int(get("max.depth.limit", "3")),
            "stopping": get("path.stopping.strategy", "maxDepth"),
            "min_gain": float(get("min.info.gain.limit", "-1.0")),
            "min_population": int(get("min.population.limit", "-1")),
            "attributes": get("split.attribute.selection.strategy",
                              "randomNotUsedYet" if forest else "notUsedYet")}


def _drawn_attributes(rng, strategy: str, attrs: List[int], used: set
                      ) -> List[int]:
    if strategy == "notUsedYet":
        return [a for a in attrs if a not in used]
    if strategy == "randomAll":
        m = max(1, int(math.sqrt(len(attrs))))
        return rng.choice(attrs, size=m, replace=False).tolist()
    if strategy == "randomNotUsedYet":
        avail = [a for a in attrs if a not in used]
        if not avail:
            return []
        m = max(1, int(math.sqrt(len(avail))))
        return rng.choice(avail, size=m, replace=False).tolist()
    return list(attrs)


def first_of_least(preds: Tuple, allowed: List[Dict], scores: List[float]
                   ) -> int:
    return int(np.argmin(scores))


def grow_tree(codes, y, w, schema: Dict, classes: int, sem: Dict, seed: int,
              choose=first_of_least) -> List[Dict]:
    """One tree over rows weighted `w`, level by level; returns its final
    paths as {"predicates": (keys..), "counts": [K] int64}, empty leaves
    left out, in leaf order. `choose(path so far, allowed splits, their
    weighted impurities)` gives the place of the split taken: a test that
    lets ties go either way hands its own."""
    splits = candidate_splits(schema)
    attrs = sorted({s["attribute"] for s in splits})
    rng = np.random.default_rng(seed)
    algo = sem["algorithm"]
    leaves = [{"preds": (), "used": set(), "keep": np.ones(len(y), bool)}]
    for _depth in range(sem["max_depth"]):
        opened = [lf for lf in leaves
                  if not lf.get("stopped") and "split" not in lf]
        if not opened:
            break
        grown = []
        for lf in opened:
            node = class_counts(y, w, lf["keep"], classes)
            pop, node_imp = int(node.sum()), impurity(node, algo)
            drawn = set(_drawn_attributes(rng, sem["attributes"], attrs,
                                          lf["used"]))
            allowed = [s for s in splits if s["attribute"] in drawn]
            if pop <= 0 or not allowed or node_imp <= 0.0:
                lf["stopped"] = True
                continue
            scores = [weighted_impurity(
                split_counts(s, codes, y, w, lf["keep"], classes), algo)
                for s in allowed]
            best = allowed[choose(lf["preds"], allowed, scores)]
            gain = node_imp - min(scores)
            if (sem["stopping"] == "minInfoGain" and sem["min_gain"] >= 0
                    and gain < sem["min_gain"]) or (
                    sem["stopping"] == "minPopulation"
                    and sem["min_population"] >= 0
                    and pop < sem["min_population"]):
                lf["stopped"] = True
                continue
            lf["split"] = best
            seg = segment_ids(best, codes)
            for s in range(best["segments"]):
                grown.append({"preds": lf["preds"] + (best["predicates"][s],),
                              "used": lf["used"] | {best["attribute"]},
                              "keep": lf["keep"] & (seg == s)})
        if not grown:
            break
        leaves += grown
    paths = []
    for lf in leaves:
        if "split" in lf:
            continue
        counts = class_counts(y, w, lf["keep"], classes)
        if counts.sum() > 0 or not lf["preds"]:
            paths.append({"predicates": lf["preds"], "counts": counts})
    return paths


def grow_forest(codes, y, weights, schema: Dict, classes: int, sem: Dict,
                seed: int = 0, choose=None) -> List[List[Dict]]:
    """Every tree of the forest: tree t over `weights[t]`, its attribute
    draws from `default_rng(seed + t)`; `choose(t)` is tree t's `choose`."""
    return [grow_tree(codes, y, weights[t], schema, classes, sem, seed + t,
                      choose(t) if choose else first_of_least)
            for t in range(len(weights))]
