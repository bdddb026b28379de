"""Decision tree / random forest: level-wise builder with tensorized splits.

Reference semantics (org.avenir.tree, SURVEY §2.3/§3.4):
- DecisionTreeBuilder is an *iterative MR job*, one tree level per run: the
  mapper routes every record through every candidate split predicate of
  every candidate attribute, emitting (path-so-far, splitId:predicate) keys;
  the reducer accumulates per-(path, split, predicate) class histograms and
  picks the min weighted-entropy/gini split per parent
  (DecisionTreeBuilder.java:258-347, :440-576). State between levels is a
  DecisionPathList JSON file rotated by resource/detr.sh:34-41.
- SplitManager enumerates candidate splits: numeric attributes partition
  [min,max] into up to maxSplit segments at splitScanInterval boundaries
  (SplitManager.java:284-391); categoricals enumerate set partitions into
  2..maxSplit groups (:397-561). Predicates serialize as "attr op value
  [otherBound]" / "attr in a:b:c" strings.
- Stopping: maxDepth / minPopulation / minInfoGain
  (DecisionPathStoppingStrategy.java:57-70). Random forest = first-pass
  sampling (with/without replacement) + per-level random attribute selection
  (DecisionTreeBuilder.java:200-236, :353-369).

TPU design: candidate splits are static (schema-driven), so each split is a
record->segment mapping computed ONCE, on the device from the feature
columns as parsed (`segment_matrix`), as an int8 matrix [n_splits, n], held
in lines of 128 rows (`to_lines`: 1 B a row and split on the device, where
a row-major [n, n_splits] is tiled to 128 lanes a row); a tree level is then one pass
over the rows in blocks, each block a one-hot int8 contraction into the
int32 histogram tensor [leaves, splits, segments, classes] — no predicate
branching, no shuffle, and counts that are exact at any row count. The
host picks best splits / applies stopping (tiny tensors) and updates the
on-device leaf assignment from the winning split's segment row. Random
forest reuses the same segment matrix across trees with per-tree row
weights (bootstrap counts) and attribute masks.

Model format: DecisionPathList-compatible JSON (jackson field names), so
reference decPathOut.txt files and ours are interchangeable.
"""

from __future__ import annotations

import itertools
import json
import math
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from avenir_tpu import obs
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureField, FeatureSchema
from avenir_tpu.native import ingest
from avenir_tpu.utils.metrics import ConfusionMatrix

ROOT_PATH = "$root"
_SAMPLE_WORKERS = 3          # threads that count bootstrap draws


def _np_bits_entropy(counts: np.ndarray, axis: int = -1) -> np.ndarray:
    """Host twin of ops.infotheory.bits_entropy for the builder's tiny
    per-level stat tensors — an eager device dispatch per level/leaf costs
    more than the arithmetic (remote-chip dispatch latency)."""
    tot = counts.sum(axis=axis, keepdims=True)
    p = counts / np.maximum(tot, 1e-12)
    h = -np.sum(np.where(p > 0, p * np.log(np.maximum(p, 1e-12)), 0.0), axis=axis)
    return h / np.log(2.0)


def _np_gini(counts: np.ndarray, axis: int = -1) -> np.ndarray:
    tot = counts.sum(axis=axis, keepdims=True)
    p = counts / np.maximum(tot, 1e-12)
    return 1.0 - np.sum(p * p, axis=axis)

# ---------------------------------------------------------------------------
# candidate split enumeration (host; SplitManager semantics)
# ---------------------------------------------------------------------------


@dataclass
class Predicate:
    """One predicate of one split segment ("attr op value [other]" form)."""

    attribute: int
    operator: str                       # ge / lt / in  (segment predicates)
    value: Optional[float] = None
    other_bound: Optional[float] = None
    cat_values: List[str] = field(default_factory=list)
    is_int: bool = True

    def to_string(self) -> str:
        if self.operator == "in":
            return f"{self.attribute} in " + ":".join(self.cat_values)
        fmt = (lambda v: str(int(v))) if self.is_int else (lambda v: str(v))
        s = f"{self.attribute} {self.operator} {fmt(self.value)}"
        if self.other_bound is not None:
            s += f" {fmt(self.other_bound)}"
        return s

    def to_json(self) -> Dict:
        obj: Dict = {"attribute": self.attribute, "operator": self.operator,
                     "predicateStr": self.to_string()}
        if self.operator == "in":
            obj["categoricalValues"] = list(self.cat_values)
        elif self.is_int:
            obj["valueInt"] = int(self.value)
            if self.other_bound is not None:
                obj["otherBoundInt"] = int(self.other_bound)
        else:
            obj["valueDbl"] = float(self.value)
            if self.other_bound is not None:
                obj["otherBoundDbl"] = float(self.other_bound)
        return obj


@dataclass
class CandidateSplit:
    """One candidate split of one attribute into `n_segments` segments.

    `segment_of` maps a raw column (numpy) to segment ids; `predicates[s]`
    is the predicate describing segment s."""

    attribute: int
    split_id: int
    n_segments: int
    predicates: List[Predicate]
    _kind: str = "numeric"
    _bounds: Optional[np.ndarray] = None        # numeric: inner boundaries
    _group_of: Optional[np.ndarray] = None      # categorical: code -> group

    def segment_of(self, col: np.ndarray) -> np.ndarray:
        if self._kind == "numeric":
            # the boundaries at or below the value, one comparison each: a
            # split has a handful, and a binary search per row costs more
            seg = np.zeros(len(col), np.int8)
            for b in self._bounds:
                seg += col >= b
            return seg
        return self._group_of.astype(np.int8).take(col)


def _numeric_splits(fld: FeatureField, max_split: int) -> List[List[float]]:
    """All partitions of [min, max] into 2..max_split segments with
    boundaries at splitScanInterval steps (SplitManager.java:284-391)."""
    lo, hi = fld.min, fld.max
    interval = fld.split_scan_interval or fld.bucket_width
    if lo is None or hi is None or not interval:
        return []
    points = []
    p = lo + interval
    while p < hi - 1e-9:
        points.append(p)
        p += interval
    out: List[List[float]] = []
    for nseg in range(2, max_split + 1):
        for combo in itertools.combinations(points, nseg - 1):
            out.append(list(combo))
    return out


def _set_partitions(items: Sequence[str], max_groups: int,
                    cap: int = 128) -> List[List[List[str]]]:
    """Partitions of a category set into 2..max_groups groups
    (SplitManager.java:397-561), capped to avoid blow-up."""
    n = len(items)
    results: List[List[List[str]]] = []
    # enumerate by group-assignment vectors in canonical form
    seen = set()
    max_groups = min(max_groups, n)

    def assignments(prefix, next_group):
        if len(results) >= cap:
            return
        if len(prefix) == n:
            ngroups = next_group
            if 2 <= ngroups <= max_groups:
                key = tuple(prefix)
                if key not in seen:
                    seen.add(key)
                    groups: List[List[str]] = [[] for _ in range(ngroups)]
                    for i, g in enumerate(prefix):
                        groups[g].append(items[i])
                    results.append(groups)
            return
        for g in range(next_group + 1):
            if g > max_groups - 1:
                continue
            assignments(prefix + [g], max(next_group, g + 1))

    assignments([], 0)
    return results


def enumerate_splits(schema: FeatureSchema,
                     cat_partition_cap: int = 128) -> List[CandidateSplit]:
    """All candidate splits of all feature attributes, in stable order."""
    splits: List[CandidateSplit] = []
    sid = 0
    for fld in schema.feature_fields:
        max_split = fld.max_split or 2
        if fld.is_numeric:
            for bounds in _numeric_splits(fld, max_split):
                preds = []
                is_int = fld.data_type == "int"
                for s in range(len(bounds) + 1):
                    if s == 0:
                        preds.append(Predicate(fld.ordinal, "lt", bounds[0],
                                               is_int=is_int))
                    elif s == len(bounds):
                        preds.append(Predicate(fld.ordinal, "ge", bounds[-1],
                                               is_int=is_int))
                    else:
                        preds.append(Predicate(fld.ordinal, "ge", bounds[s - 1],
                                               other_bound=bounds[s], is_int=is_int))
                splits.append(CandidateSplit(
                    fld.ordinal, sid, len(bounds) + 1, preds,
                    _kind="numeric", _bounds=np.asarray(bounds),
                ))
                sid += 1
        elif fld.is_categorical and len(fld.cardinality) >= 2:
            for groups in _set_partitions(fld.cardinality, max_split,
                                          cap=cat_partition_cap):
                group_of = np.zeros(len(fld.cardinality), np.int64)
                preds = []
                index = fld.cardinality_index()
                for g, members in enumerate(groups):
                    for m in members:
                        group_of[index[m]] = g
                    preds.append(Predicate(fld.ordinal, "in",
                                           cat_values=list(members)))
                splits.append(CandidateSplit(
                    fld.ordinal, sid, len(groups), preds,
                    _kind="categorical", _group_of=group_of,
                ))
                sid += 1
    return splits


# ---------------------------------------------------------------------------
# the level histogram kernel
# ---------------------------------------------------------------------------


#: the device holds every per-row array in lines of LANES rows, [.., R,
#: LANES]: the TPU tiles the last two axes (8 x 128 int32, 32 x 128 int8),
#: so a [T, n] array of ten trees would be padded to sixteen and an [n, NS]
#: one to 128 lanes a row; in lines nothing is padded but the last tile
LANES = 128
#: rows of one block of the level pass: what a pass holds beyond its
#: arguments is one block's one-hot operands, whatever the row count
ROW_BLOCK = 1 << 17
_BLOCK_LINES = ROW_BLOCK // LANES
_DIGIT_BITS = 7                     # an int8 operand holds 0..127
MAX_WEIGHT = (1 << 31) - 1


def to_lines(x: np.ndarray) -> np.ndarray:
    """[.., n] -> [.., R, LANES] on the host: the last axis padded with
    zeros to whole lines (a pad row has weight 0 and counts nowhere)."""
    n = x.shape[-1]
    pad = -n % LANES
    if pad:
        x = np.concatenate(
            [x, np.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    return x.reshape(x.shape[:-1] + ((n + pad) // LANES, LANES))


def _weight_digits(max_weight: int) -> int:
    """Base-128 digits of the largest row weight (1 for any bootstrap
    count): a static argument of the level pass."""
    return max(1, -(-int(max_weight).bit_length() // _DIGIT_BITS))


def whole_weights(row_weights, n: int) -> np.ndarray:
    """Row weights as the int32 counts the level pass adds up. A weight is
    a count (1, or how often a bootstrap sample drew the row): fractions,
    negatives and counts past int32 are refused, because the histogram
    counts in integers and would otherwise be silently wrong."""
    if row_weights is None:
        return np.ones(n, np.int32)
    w = np.asarray(row_weights)
    if w.shape != (n,):
        raise ValueError(f"row_weights wants one weight a row ({n}), "
                         f"got shape {w.shape}")
    if w.size and not (np.all(w == np.floor(w)) and w.min() >= 0
                       and w.max() <= MAX_WEIGHT):
        raise ValueError(
            "row_weights are counts: whole numbers from 0 to 2^31 - 1 "
            "(the level histogram counts in integers)")
    return w.astype(np.int32)


def _block_counts(leaf_ids, seg_matrix, labels, weights,
                  n_leaves: int, smax: int, k: int, digits: int):
    """One block's [T, L*K, NS*S] int32 counts, the block being b lines of
    every argument. The row side is the one-hot of (leaf, class) carrying
    the weight's base-128 digits, the split side the one-hot of each
    split's segment, both int8; their contraction over the block's rows
    accumulates in int32 on the MXU, so nothing is rounded anywhere."""
    ns, b, lanes = seg_matrix.shape
    lk = leaf_ids * k + labels[None]                              # [T, b, C]
    cells = jnp.arange(n_leaves * k, dtype=jnp.int32)
    hot = lk[:, None] == cells[None, :, None, None]               # [T, LK, b, C]
    shifts = _DIGIT_BITS * jnp.arange(digits, dtype=jnp.int32)
    digit = (weights[:, None] >> shifts[None, :, None, None]) & 127
    rows = jnp.where(hot[:, None], digit[:, :, None], 0
                     ).astype(jnp.int8)                           # [T, D, LK, b, C]
    segs = (seg_matrix[:, None]
            == jnp.arange(smax, dtype=jnp.int8)[None, :, None, None]
            ).astype(jnp.int8).reshape(ns * smax, b, lanes)
    per_digit = jnp.einsum("tdmrc,nrc->tdmn", rows, segs,
                           preferred_element_type=jnp.int32)
    return jnp.sum(per_digit << shifts[None, :, None, None], axis=1)


def _lines_of(x, lo, size):
    """`size` lines of x from line `lo` (the axis before the lanes)."""
    return jax.lax.dynamic_slice_in_dim(x, lo, size, axis=x.ndim - 2)


@partial(jax.jit, static_argnames=("n_leaves", "smax", "k", "digits",
                                   "block_lines"))
def _level_histogram_forest(leaf_ids, seg_matrix, labels, weights,
                            n_leaves: int, smax: int, k: int,
                            digits: int = 1,
                            block_lines: int = _BLOCK_LINES):
    """counts[T, L, NS, S, K], int32: every tree's class histogram of all
    leaves x splits x segments in ONE dispatch — the whole MR shuffle of
    one tree level (detr.sh:34-54), for the whole forest.

    All arguments in lines (`to_lines`): leaf_ids [T, R, LANES] int32,
    seg_matrix [NS, R, LANES] int8, labels [R, LANES] int32, weights
    [T, R, LANES] int32 whole numbers under 128**digits. The trees differ
    only in leaf routing and bootstrap weights; the segment matrix and
    the labels are shared.

    The rows go through in blocks of ROW_BLOCK (`block_lines` lines; a
    test hands a smaller one) inside this one program (a `fori_loop` over
    slices of the arguments, then the lines past the last whole block),
    so what the pass holds beyond its arguments does not grow with n. It accumulates in int32: counts are exact for any n
    and any whole-number weights whose cell sums stay under 2^31. float32
    stops counting at 2^24 (16,777,216 + 1 = 16,777,216), which a root
    cell passes at 17M rows; a per-block float32 sum added up in integers
    would be exact only while a block's cell stays under 2^24; and the
    int8 contraction is the MXU's fastest form anyway."""
    t, r, _ = leaf_ids.shape
    ns = seg_matrix.shape[0]
    block = max(1, min(block_lines, r))
    whole = r // block

    def counts_of(lo, size):
        return _block_counts(
            _lines_of(leaf_ids, lo, size), _lines_of(seg_matrix, lo, size),
            _lines_of(labels, lo, size), _lines_of(weights, lo, size),
            n_leaves, smax, k, digits)

    acc = jnp.zeros((t, n_leaves * k, ns * smax), jnp.int32)
    if whole:
        acc = jax.lax.fori_loop(
            0, whole, lambda i, a: a + counts_of(i * block, block), acc)
    if r - whole * block:
        acc = acc + counts_of(whole * block, r - whole * block)
    return acc.reshape(t, n_leaves, k, ns, smax).transpose(0, 1, 3, 4, 2)


def _level_histogram(leaf_id, seg_matrix, labels, weights,
                     n_leaves: int, smax: int, k: int, digits: int = 1):
    """counts[L, NS, S, K] of one tree: the forest's pass with one tree
    (leaf_id and weights [R, LANES]); there is no second form."""
    return _level_histogram_forest(
        leaf_id[None], seg_matrix, labels, weights[None],
        n_leaves=n_leaves, smax=smax, k=k, digits=digits)[0]


def _lookup(table, index):
    """table[t, index[t, ..]] for a small table [T, L]: a compare and a
    sum over L (the TPU has no fast gather, and L is a handful)."""
    slots = jnp.arange(table.shape[1], dtype=index.dtype)
    return jnp.sum(jnp.where(index[:, None] == slots[None, :, None, None],
                             table[:, :, None, None], 0), axis=1)


def _advance_block(leaf_ids, seg_matrix, best_split_of_leaf, child_offset):
    split = _lookup(best_split_of_leaf, leaf_ids)                 # [T, b, C]
    off = _lookup(child_offset, leaf_ids)
    splits = jnp.arange(seg_matrix.shape[0], dtype=jnp.int32)
    seg = jnp.sum(jnp.where(split[:, None] == splits[None, :, None, None],
                            seg_matrix[None].astype(jnp.int32), 0), axis=1)
    return jnp.where(split >= 0, off + seg, leaf_ids)


@partial(jax.jit, donate_argnums=(0,), static_argnames=("block_lines",))
def _advance_leaves_forest(leaf_ids, seg_matrix, best_split_of_leaf,
                           child_offset, block_lines: int = _BLOCK_LINES):
    """new_leaf = child_offset[leaf] + segment under the leaf's chosen
    split, for every tree (leaf ids [T, R, LANES], tables [T, L]); leaves
    without a split (stopped/unsplit, best -1) keep their id. Written
    over the donated leaf ids block by block, like the level pass."""
    r = leaf_ids.shape[1]
    block = max(1, min(block_lines, r))
    whole = r // block

    def advanced(ids, lo, size):
        new = _advance_block(_lines_of(ids, lo, size),
                             _lines_of(seg_matrix, lo, size),
                             best_split_of_leaf, child_offset)
        return jax.lax.dynamic_update_slice_in_dim(ids, new, lo, axis=1)

    if whole:
        leaf_ids = jax.lax.fori_loop(
            0, whole, lambda i, ids: advanced(ids, i * block, block),
            leaf_ids)
    if r - whole * block:
        leaf_ids = advanced(leaf_ids, whole * block, r - whole * block)
    return leaf_ids


#: a categorical column's group is read by a chain of selects over its
#: codes while it has at most this many, by a gather of the table past it.
#: On a TPU v5e the chain costs 1.6 ps a row, split and code and the
#: gather 7.8 ns a row and split whatever the codes (from 128 on; under
#: that XLA turns the gather into the same selects), so the gather wins
#: nowhere under some 5,000 codes; but the chain is unrolled into the
#: program, which compiles 10 ms a select (2.6 s a split at 256 codes, the
#: most measured: PERF.md, PR 32), and that is what bounds it here
_SELECT_CODES = 256


def up32(bounds) -> np.ndarray:
    """The smallest float32 not below each float64 bound: for a float32
    `x`, `float64(x) >= b` exactly when `x >= up32(b)`, so the device
    compares the column as parsed and agrees with `segment_of` on every
    value, NaN among them (segment 0 on both sides)."""
    b = np.asarray(bounds, np.float64)
    with np.errstate(over="ignore"):
        f = b.astype(np.float32)
    return np.where(f < b, np.nextafter(f, np.float32(np.inf)), f)


def _segment_tables(splits: Sequence[CandidateSplit]):
    """What `_segment_lines` takes: the columns some split reads, as
    (attribute, dtype as parsed) in attribute order; per split (its column
    among them, numeric or not, its bounds or its column's codes), which
    is the schema's and static; and the tables it takes as arguments,
    bounds [NS, KB] float32 rounded up once (`up32`) and padded with +inf,
    groups [NS, V] int8."""
    attrs = sorted({sp.attribute for sp in splits})
    plan = tuple(
        (attrs.index(sp.attribute), sp._kind == "numeric",
         len(sp._bounds if sp._kind == "numeric" else sp._group_of))
        for sp in splits)
    dtypes = {c: np.float32 if numeric else np.int32 for c, numeric, _ in plan}
    bounds = np.full((len(splits), max([w for _, num, w in plan if num],
                                       default=1)), np.inf, np.float32)
    groups = np.zeros((len(splits), max([w for _, num, w in plan if not num],
                                        default=1)), np.int8)
    for i, (sp, (_, numeric, width)) in enumerate(zip(splits, plan)):
        if numeric:
            bounds[i, :width] = up32(sp._bounds)
        else:
            groups[i, :width] = sp._group_of
    columns = [(a, dtypes[c]) for c, a in enumerate(attrs)]
    return columns, plan, bounds, groups


@partial(jax.jit, static_argnames=("plan",))
def _segment_lines(cols, bounds, groups, n, plan):
    """[NS, R, LANES] int8: `CandidateSplit.segment_of` of every split over
    its column, elementwise, on feature columns [R, LANES] as the parser
    left them (float32 numeric, int32 codes). Rows from `n` on read 0."""
    r, lanes = cols[0].shape
    line = jnp.arange(r, dtype=jnp.int32)[:, None]
    lane = jnp.arange(lanes, dtype=jnp.int32)[None]
    real = (line < n // lanes) | ((line == n // lanes) & (lane < n % lanes))
    segs = []
    for s, (c, numeric, width) in enumerate(plan):
        x = cols[c]
        if numeric:
            seg = sum((x >= bounds[s, k]).astype(jnp.int8)
                      for k in range(width))
        elif width <= _SELECT_CODES:
            seg = sum(jnp.where(x == v, groups[s, v], 0)
                      for v in range(width))
        else:
            seg = jnp.take(groups[s], x, mode="clip")
        segs.append(jnp.where(real, seg, 0).astype(jnp.int8))
    return jnp.stack(segs)


def _segments_note(splits: Sequence[CandidateSplit]) -> Dict:
    """The `tree.segments` span's attributes."""
    return {"columns": len({sp.attribute for sp in splits}),
            "splits": len(splits), "device": True}


def segment_matrix(splits: Sequence[CandidateSplit], ds: Dataset,
                   put=jnp.asarray) -> jax.Array:
    """[NS, R, LANES] int8 on the device: every row's segment under every
    candidate split, in lines as the level pass reads them (1 B a row and
    split), computed there by `_segment_lines` from the columns some split
    reads. `put` places a column's lines (a mesh shards them, and the
    program, being elementwise, runs on each shard). Nothing is waited
    for, and the columns' device buffers go when the program has run."""
    n = len(ds)
    if not splits:
        return jnp.zeros((0, -(-n // LANES), LANES), jnp.int8)
    columns, plan, bounds, groups = _segment_tables(splits)
    cols = tuple(put(to_lines(np.asarray(ds.column(a), dtype)))
                 for a, dtype in columns)
    return _segment_lines(cols, jnp.asarray(bounds), jnp.asarray(groups),
                          np.int32(n), plan=plan)


# ---------------------------------------------------------------------------
# model: DecisionPathList-compatible
# ---------------------------------------------------------------------------


@dataclass
class DecisionPath:
    predicates: List[Predicate]        # empty -> root
    population: int
    info_content: float
    stopped: bool
    class_val_pr: Dict[str, float]

    def to_json(self) -> Dict:
        return {
            "predicates": [p.to_json() for p in self.predicates] or None,
            "population": int(self.population),
            "infoContent": float(self.info_content),
            "stopped": bool(self.stopped),
            "classValPr": {k: float(v) for k, v in self.class_val_pr.items()},
        }


class DecisionPathList:
    """The JSON tree model (reference tree/DecisionPathList.java format)."""

    def __init__(self, paths: List[DecisionPath]):
        self.paths = paths

    def to_json(self) -> Dict:
        return {"decisionPaths": [p.to_json() for p in self.paths]}

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh, indent=1)

    @classmethod
    def from_json(cls, obj: Dict) -> "DecisionPathList":
        paths = []
        for p in obj["decisionPaths"]:
            preds = []
            for pr in (p.get("predicates") or []):
                op = pr["operator"]
                if op == "in":
                    pred = Predicate(pr["attribute"], "in",
                                     cat_values=pr.get("categoricalValues", []))
                elif "valueInt" in pr and pr.get("valueInt") is not None:
                    pred = Predicate(pr["attribute"], op,
                                     value=pr["valueInt"],
                                     other_bound=pr.get("otherBoundInt"),
                                     is_int=True)
                else:
                    pred = Predicate(pr["attribute"], op,
                                     value=pr.get("valueDbl"),
                                     other_bound=pr.get("otherBoundDbl"),
                                     is_int=False)
                preds.append(pred)
            paths.append(DecisionPath(
                preds, p.get("population", 0), p.get("infoContent", 0.0),
                p.get("stopped", False), p.get("classValPr", {}) or {},
            ))
        return cls(paths)

    @classmethod
    def load(cls, path: str) -> "DecisionPathList":
        with open(path) as fh:
            return cls.from_json(json.load(fh))

    # ------------------------------------------------------------ prediction
    def predict(self, ds: Dataset, class_values: List[str]) -> np.ndarray:
        """Route every record down its matching path; argmax classValPr."""
        n = len(ds)
        pred = np.zeros(n, np.int32)
        assigned = np.zeros(n, bool)
        for path in self.paths:
            mask = np.ones(n, bool)
            for pr in path.predicates:
                col = ds.column(pr.attribute)
                if pr.operator == "in":
                    fld = ds.schema.field_by_ordinal(pr.attribute)
                    codes = {fld.cardinality_index()[v] for v in pr.cat_values
                             if v in fld.cardinality_index()}
                    mask &= np.isin(col.astype(np.int64), list(codes))
                else:
                    x = col.astype(np.float64)
                    if pr.operator == "ge":
                        m = x >= pr.value
                        if pr.other_bound is not None:
                            m &= x < pr.other_bound
                    elif pr.operator == "lt":
                        m = x < pr.value
                        if pr.other_bound is not None:
                            m &= x >= pr.other_bound
                    elif pr.operator == "gt":
                        m = x > pr.value
                        if pr.other_bound is not None:
                            m &= x <= pr.other_bound
                    else:  # le
                        m = x <= pr.value
                        if pr.other_bound is not None:
                            m &= x > pr.other_bound
                    mask &= m
            if path.class_val_pr:
                best = max(path.class_val_pr.items(), key=lambda kv: kv[1])[0]
                ci = class_values.index(best)
                take = mask & ~assigned
                pred[take] = ci
                assigned |= mask
        return pred


# ---------------------------------------------------------------------------
# device path evaluation (tensorized predict)
# ---------------------------------------------------------------------------

_OP_CODE = {"ge": 0, "lt": 1, "gt": 2, "le": 3}


@partial(jax.jit, static_argnames=())
def _path_match_kernel(x_num, x_cat, kind, col, op, val, other, member):
    """matches[n, T, P]: does row n satisfy every predicate of path P of
    tree T. One batched comparison routes all rows through all paths'
    predicates at once — the device twin of the reference's pass-through
    classify (DecisionTreeBuilder.java:700-705) without the per-path host
    loop.

    x_num f32 [n, An], x_cat i32 [n, Ac]; predicate tables [T, P, D]
    (+ member [T, P, D, B]); kind 0 = unused slot (always true)."""
    xn = x_num[:, None, None, None, :]            # [n,1,1,1,An]
    xv = jnp.take_along_axis(
        jnp.broadcast_to(xn, xn.shape[:3] + (1, xn.shape[-1])),
        jnp.maximum(col, 0)[None, ..., None], axis=-1)[..., 0]   # [n,T,P,D]
    v, o = val[None], other[None]
    ge = xv >= v
    lt = xv < v
    gt = xv > v
    le = xv <= v
    has_other = jnp.isfinite(o)
    num_ok = jnp.select(
        [op[None] == 0, op[None] == 1, op[None] == 2],
        [ge & jnp.where(has_other, xv < o, True),
         lt & jnp.where(has_other, xv >= o, True),
         gt & jnp.where(has_other, xv <= o, True)],
        le & jnp.where(has_other, xv > o, True),
    )
    code = jnp.take_along_axis(
        jnp.broadcast_to(x_cat[:, None, None, None, :],
                         (x_cat.shape[0],) + col.shape + (x_cat.shape[1],)),
        jnp.maximum(col, 0)[None, ..., None], axis=-1)[..., 0]   # [n,T,P,D]
    cat_ok = jnp.take_along_axis(
        jnp.broadcast_to(member[None],
                         (x_cat.shape[0],) + member.shape),
        jnp.clip(code, 0, member.shape[-1] - 1)[..., None], axis=-1)[..., 0]
    ok = jnp.where(kind[None] == 1, num_ok,
                   jnp.where(kind[None] == 2, cat_ok, True))
    return jnp.all(ok, axis=-1)                   # [n, T, P]


class DevicePathEvaluator:
    """Tensorized application of one or more DecisionPathList models.

    Compiles the trees' predicate chains into padded tables [T, P, D]
    (trees x paths x chain depth) so prediction is one jitted kernel:
    every row x every path evaluates as a batched comparison, first
    matching path in path order wins (the host predict's assignment
    order), and a forest majority-votes across the tree axis."""

    def __init__(self, trees: Sequence[DecisionPathList],
                 schema: FeatureSchema, class_values: List[str]):
        self.schema = schema
        self.class_values = class_values
        num_fields = [f for f in schema.feature_fields if f.is_numeric]
        cat_fields = [f for f in schema.feature_fields if f.is_categorical]
        self.num_fields, self.cat_fields = num_fields, cat_fields
        num_col = {f.ordinal: i for i, f in enumerate(num_fields)}
        cat_col = {f.ordinal: i for i, f in enumerate(cat_fields)}
        bmax = max((len(f.cardinality) for f in cat_fields), default=1)
        t = len(trees)
        p = max((len(tr.paths) for tr in trees), default=1) or 1
        d = max((len(pa.predicates) for tr in trees for pa in tr.paths),
                default=1) or 1
        kind = np.zeros((t, p, d), np.int8)
        col = np.zeros((t, p, d), np.int32)
        op = np.zeros((t, p, d), np.int8)
        val = np.zeros((t, p, d), np.float32)
        other = np.full((t, p, d), np.nan, np.float32)
        member = np.ones((t, p, d, bmax), bool)
        path_class = np.zeros((t, p), np.int32)
        path_valid = np.zeros((t, p), bool)
        for ti, tr in enumerate(trees):
            for pi, pa in enumerate(tr.paths):
                if pa.class_val_pr:
                    best = max(pa.class_val_pr.items(), key=lambda kv: kv[1])[0]
                    path_class[ti, pi] = class_values.index(best)
                    path_valid[ti, pi] = True
                for di, pr in enumerate(pa.predicates):
                    if pr.operator == "in":
                        kind[ti, pi, di] = 2
                        col[ti, pi, di] = cat_col[pr.attribute]
                        fld = schema.field_by_ordinal(pr.attribute)
                        idx = fld.cardinality_index()
                        row = np.zeros(bmax, bool)
                        for v in pr.cat_values:
                            if v in idx:
                                row[idx[v]] = True
                        member[ti, pi, di] = row
                    else:
                        kind[ti, pi, di] = 1
                        col[ti, pi, di] = num_col[pr.attribute]
                        op[ti, pi, di] = _OP_CODE[pr.operator]
                        val[ti, pi, di] = pr.value
                        if pr.other_bound is not None:
                            other[ti, pi, di] = pr.other_bound
        self.tables = tuple(jnp.asarray(a) for a in
                            (kind, col, op, val, other, member))
        self.path_class = jnp.asarray(path_class)
        self.path_valid = jnp.asarray(path_valid)
        self.n_trees = t

    def _features(self, ds: Dataset):
        # a dummy column keeps the gather axes non-empty for schemas with
        # no numeric (or no categorical) features; kind masks it out
        x_num = np.stack(
            [ds.column(f.ordinal).astype(np.float32) for f in self.num_fields],
            axis=1) if self.num_fields else np.zeros((len(ds), 1), np.float32)
        x_cat = np.stack(
            [ds.column(f.ordinal).astype(np.int32) for f in self.cat_fields],
            axis=1) if self.cat_fields else np.zeros((len(ds), 1), np.int32)
        # host arrays: per_tree_predict transfers one row block at a time,
        # so device memory stays bounded at any corpus size
        return x_num, x_cat

    def per_tree_predict(self, ds: Dataset,
                         row_block: int = 262_144) -> np.ndarray:
        """[n, T] predicted class codes, first matching path in path order
        (rows matching no valid path predict class 0, as the host loop).
        Rows evaluate in `row_block` chunks: the kernel's broadcast
        intermediates are O(rows x trees x paths x depth), so blocking
        keeps device memory bounded at any corpus size."""
        x_num, x_cat = self._features(ds)
        out = []
        for s in range(0, len(ds), row_block):
            matches = _path_match_kernel(jnp.asarray(x_num[s:s + row_block]),
                                         jnp.asarray(x_cat[s:s + row_block]),
                                         *self.tables)
            matches = matches & self.path_valid[None]
            first = jnp.argmax(matches, axis=-1)                # [b, T]
            pred = jnp.take_along_axis(
                jnp.broadcast_to(self.path_class[None], matches.shape),
                first[..., None], axis=-1)[..., 0]
            any_match = matches.any(axis=-1)
            out.append(np.asarray(
                jnp.where(any_match, pred, 0).astype(jnp.int32)))
        return np.concatenate(out) if out else np.zeros((0, self.n_trees),
                                                        np.int32)

    def predict(self, ds: Dataset) -> np.ndarray:
        """[n] class codes: single tree pass-through, or majority vote
        across trees (RandomForestBuilder.predict semantics)."""
        per_tree = self.per_tree_predict(ds)
        if self.n_trees == 1:
            return per_tree[:, 0]
        k = len(self.class_values)
        votes = np.zeros((per_tree.shape[0], k), np.int64)
        rows = np.arange(per_tree.shape[0], dtype=np.int32)
        for t in range(per_tree.shape[1]):
            votes[rows, per_tree[:, t]] += 1
        return votes.argmax(axis=1).astype(np.int32)


# ---------------------------------------------------------------------------
# the level loop
# ---------------------------------------------------------------------------


def _grow_forest(builders: Sequence["DecisionTreeBuilder"], seg_d, labels_d,
                 ws_d, leaf_ids, digits: int, note: Dict,
                 mesh=None) -> List["DecisionPathList"]:
    """Grow the builders' trees together over shared rows: per level one
    histogram pass for all trees, the host's split selection, one advance
    of the [T, n] leaf ids; then a last pass for the paths' final counts.
    `note` is the `tree.fit` span's attributes (`levels`, `leaves`)."""
    b0 = builders[0]
    ns, k, smax = len(b0.splits), len(b0.class_values), b0.smax

    def level_counts(leaves_t) -> np.ndarray:
        """[T, lpad, NS, S, K] int64 on the host. The leaf axis is padded
        to the next power of two: n_leaves is a static (compile-time)
        dimension, and letting it take every integer value would
        recompile the pass per level and per tree; padded leaf ids
        receive no rows."""
        lpad = 1 << (max(len(lv) for lv in leaves_t) - 1).bit_length()
        with obs.span("tree.level.dispatch", leaves=lpad):
            if mesh is not None:
                from avenir_tpu.parallel.distributed import (
                    distributed_tree_level_fn)

                out = distributed_tree_level_fn(mesh, lpad, ns, smax, k, digits)(
                    leaf_ids[0], seg_d, labels_d, ws_d[0])[None]
            else:
                out = _level_histogram_forest(
                    leaf_ids, seg_d, labels_d, ws_d,
                    n_leaves=lpad, smax=smax, k=k, digits=digits)
        with obs.span("tree.level.fetch"):
            return np.asarray(out).astype(np.int64)

    leaves_t: List[List[Dict]] = [
        [{"preds": [], "used": set(), "stopped": False}] for _ in builders]
    levels = 0
    for _depth in range(b0.max_depth if ns else 0):
        if not any(DecisionTreeBuilder._active_leaves(lv) for lv in leaves_t):
            break
        counts_all = level_counts(leaves_t)
        levels += 1
        lpad = counts_all.shape[1]
        with obs.span("tree.level.select"):
            bests, offsets = [], []
            any_new = False
            for t, b in enumerate(builders):
                best, child, new_l = b._grow_level(
                    leaves_t[t], counts_all[t][: len(leaves_t[t])], lpad)
                if new_l:
                    any_new = True
                    # children get smax slots per split parent
                    leaves_t[t] = leaves_t[t] + new_l
                bests.append(best)
                offsets.append(child)
        if not any_new:
            break
        with obs.span("tree.level.advance"):
            leaf_ids = _advance_leaves_forest(
                leaf_ids, seg_d, jnp.asarray(np.stack(bests)),
                jnp.asarray(np.stack(offsets)))

    counts_fin = level_counts(leaves_t) if ns else None
    levels += bool(ns)
    with obs.span("tree.emit"):
        trees = [
            b._emit_paths(leaves_t[t],
                          counts_fin[t][: len(leaves_t[t])]
                          if counts_fin is not None else None)
            for t, b in enumerate(builders)]
    note.update(levels=levels, leaves=sum(len(tr.paths) for tr in trees))
    return trees


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------


class DecisionTreeBuilder:
    """dtb.* job equivalent: level-wise tree growth, all state in-process."""

    def __init__(
        self,
        schema: FeatureSchema,
        split_algorithm: str = "entropy",          # or giniIndex
        max_depth: int = 3,
        min_info_gain: float = -1.0,
        min_population: int = -1,
        stopping_strategy: str = "maxDepth",
        attr_selection_strategy: str = "notUsedYet",
        cat_partition_cap: int = 128,
        seed: int = 0,
    ):
        self.schema = schema
        self.algo = split_algorithm
        self.max_depth = max_depth
        self.min_info_gain = min_info_gain
        self.min_population = min_population
        self.stopping = stopping_strategy
        self.attr_strategy = attr_selection_strategy
        self.class_values = schema.class_values()
        self.splits = enumerate_splits(schema, cat_partition_cap)
        self.smax = max((s.n_segments for s in self.splits), default=2)
        self.rng = np.random.default_rng(seed)

    # ------------------------------------------------------------------- fit
    def fit(self, ds: Dataset, row_weights: Optional[np.ndarray] = None,
            mesh=None) -> DecisionPathList:
        """Build the tree. `row_weights` are counts (`whole_weights`:
        fractions are refused). With `mesh`, the row tensors shard over the
        mesh and every level histogram is the same pass on each shard's
        rows, psum'd (`distributed_tree_level_fn`: the reference's
        shuffle); zero-weight rows pad to shard divisibility, so counts
        are exact."""
        n = len(ds)
        with obs.span("tree.fit", rows=n, trees=1, splits=len(self.splits),
                      row_block=min(ROW_BLOCK, n)) as note:
            if mesh is not None:
                from avenir_tpu.parallel.mesh import shard_rows

                # the lines shard; a line of padding weighs 0
                put = partial(shard_rows, mesh)
            else:
                put = jnp.asarray
            with obs.span("tree.segments", **_segments_note(self.splits)):
                seg_d = segment_matrix(self.splits, ds, put)
                labels = to_lines(ds.labels())
                w_host = whole_weights(row_weights, n)
            digits = _weight_digits(w_host.max(initial=1))
            w_host = to_lines(w_host)
            with obs.span("tree.put"):
                issued = obs.now()
                labels_d = put(labels)
                if mesh is not None:
                    ws_d = shard_rows(mesh, w_host[None], axis=1)
                    leaf_ids = shard_rows(mesh, np.zeros_like(labels)[None],
                                          axis=1)
                else:
                    ws_d = jnp.asarray(w_host)[None]
                    leaf_ids = jnp.zeros((1,) + labels.shape, jnp.int32)
            obs.landed("tree.put.landed", (labels_d, ws_d, leaf_ids), issued)
            return _grow_forest([self], seg_d, labels_d, ws_d, leaf_ids,
                                digits, note, mesh)[0]

    @staticmethod
    def _active_leaves(leaves: List[Dict]) -> List[int]:
        return [i for i, lf in enumerate(leaves)
                if not lf["stopped"] and "split" not in lf]

    def _grow_level(self, leaves: List[Dict], counts: np.ndarray, lpad: int
                    ) -> Tuple[np.ndarray, np.ndarray, List[Dict]]:
        """Host-side split selection for one level, given the [L, NS, S, K]
        class histogram of every (leaf, candidate split, segment). Returns
        (best_split_of_leaf [lpad], child_offset [lpad], new_leaves);
        mutates `leaves` entries (split chosen / stopped)."""
        k = len(self.class_values)
        ns = len(self.splits)
        impurity_fn = (_np_bits_entropy if self.algo in ("entropy", "infoGain")
                       else _np_gini)
        seg_tot = counts.sum(axis=3)                      # [L, NS, S]
        leaf_tot = seg_tot.sum(axis=2)                    # [L, NS] (same per split)

        # weighted impurity per (leaf, split)
        imp = impurity_fn(counts, axis=-1)                # [L,NS,S]
        wimp = (seg_tot * imp).sum(axis=2) / np.maximum(leaf_tot, 1e-9)

        # lpad-sized for the same compile-stability reason as counts
        best_split_of_leaf = np.full(lpad, -1, np.int32)
        child_offset = np.full(lpad, -1, np.int32)
        new_leaves: List[Dict] = []

        for li in self._active_leaves(leaves):
            lf = leaves[li]
            pop = float(leaf_tot[li].max())
            # class counts of this leaf: any split column's segment-sum
            cls_counts = (counts[li, 0].sum(axis=0) if ns
                          else np.zeros(k, np.float64))
            node_imp = float(impurity_fn(cls_counts))

            allowed = self._allowed_splits(lf)
            if pop <= 0 or not allowed or node_imp <= 0.0:
                # pure nodes cannot improve; splitting them only burns
                # device passes and bloats the path list
                lf["stopped"] = True
                continue
            cand = wimp[li, allowed]
            bi = int(allowed[int(np.argmin(cand))])
            gain = node_imp - float(wimp[li, bi])

            # stopping strategies (DecisionPathStoppingStrategy.java:57-70;
            # maxDepth is enforced by the level-loop bound itself)
            stop = False
            if self.stopping == "minInfoGain" and self.min_info_gain >= 0:
                stop = gain < self.min_info_gain
            elif self.stopping == "minPopulation" and self.min_population >= 0:
                stop = pop < self.min_population
            if stop:
                lf["stopped"] = True
                continue

            sp = self.splits[bi]
            best_split_of_leaf[li] = bi
            child_offset[li] = len(leaves) + len(new_leaves)
            for s in range(self.smax):
                if s < sp.n_segments:
                    new_leaves.append({
                        "preds": lf["preds"] + [sp.predicates[s]],
                        "used": lf["used"] | {sp.attribute},
                        "stopped": False,
                    })
                else:
                    # pad children so child ids stay contiguous per leaf;
                    # never emitted as paths (no rows can route here)
                    new_leaves.append({"preds": lf["preds"], "used": lf["used"],
                                       "stopped": True, "pad": True})
            lf["split"] = bi           # parent becomes an internal node
        return best_split_of_leaf, child_offset, new_leaves

    def _emit_paths(self, leaves: List[Dict],
                    counts_final: Optional[np.ndarray]) -> DecisionPathList:
        """Final paths: any leaf never split, with class distribution from
        the final level histogram."""
        k = len(self.class_values)
        model_paths: List[DecisionPath] = []
        for li, lf in enumerate(leaves):
            if "split" in lf or lf.get("pad"):
                continue                   # internal node / padded child slot
            cls_counts = (
                counts_final[li, 0].sum(axis=0)
                if counts_final is not None else np.zeros(k, np.float64)
            )
            tot = cls_counts.sum()
            if tot <= 0 and lf["preds"]:
                continue                   # padded/empty child
            pr = {
                self.class_values[c]: (float(cls_counts[c]) / tot if tot else 0.0)
                for c in range(k)
            }
            info = float(
                (_np_bits_entropy if self.algo in ("entropy", "infoGain")
                 else _np_gini)(cls_counts))
            model_paths.append(DecisionPath(
                lf["preds"], int(tot), info, True, pr
            ))
        return DecisionPathList(model_paths)

    def _allowed_splits(self, leaf: Dict) -> List[int]:
        strat = self.attr_strategy
        used = leaf["used"]
        attrs = sorted({sp.attribute for sp in self.splits})
        if strat == "all":
            chosen = set(attrs)
        elif strat == "notUsedYet":
            # exhausted attributes stop the leaf rather than re-splitting on
            # an already-used attribute (which yields duplicate predicates)
            chosen = set(a for a in attrs if a not in used)
        elif strat == "randomAll":
            m = max(1, int(math.sqrt(len(attrs))))
            chosen = set(self.rng.choice(attrs, size=m, replace=False).tolist())
        elif strat == "randomNotUsedYet":
            avail = [a for a in attrs if a not in used]
            if not avail:
                return []
            m = max(1, int(math.sqrt(len(avail))))
            chosen = set(self.rng.choice(avail, size=m, replace=False).tolist())
        else:
            chosen = set(attrs)
        return [i for i, sp in enumerate(self.splits) if sp.attribute in chosen]


# ---------------------------------------------------------------------------
# random forest
# ---------------------------------------------------------------------------


class RandomForestBuilder:
    """RF = trees over bootstrap row weights + random attribute selection
    (reference first-iteration sampling DecisionTreeBuilder.java:200-236 with
    sub.sampling.strategy withReplace/withoutReplace).

    The sampling rule is the job's contract: **the forest is a function of
    the input file and the seed.** One `np.random.default_rng(seed)` serves
    all trees, in tree order. Under `withReplace` tree t's sample is the
    values of the t-th `rng.integers(0, n, n)`, and a row's weight is how
    often it was drawn (`np.bincount(idx, minlength=n)`); under
    `withoutReplace` the weight is `rng.random(n) < sample_rate`; any
    other strategy weighs every row 1 and draws nothing. Tree t picks its
    node attributes with a generator of its own,
    `np.random.default_rng(seed + t)`, through `choice` in
    `DecisionTreeBuilder._allowed_splits`. The contract is those values,
    not who computes them or in what order: the numpy loop in `_sample` is
    the written rule, and where it can the native library walks the same
    stream by position on every core (`ingest.bootstrap_counts_native`). That walk
    rests on three facts of numpy that no interface states: `default_rng`
    is PCG64, whose 64-bit outputs `integers` takes as two 32-bit values,
    the low half first, while `n - 1 < 2^32 - 1`; each value x gives the
    draw `(x * n) >> 32` by Lemire's rule; and x is thrown away when
    `uint32(x * n) < (2^32 - n) mod n`. A numpy that changes one of them
    is noticed by the test that holds the walk equal to the loop, element
    for element (`tests/test_tree.py`), and by nothing else."""

    def __init__(
        self,
        schema: FeatureSchema,
        num_trees: int = 10,
        sampling: str = "withReplace",
        sample_rate: float = 0.7,
        seed: int = 0,
        **tree_kwargs,
    ):
        self.schema = schema
        self.num_trees = num_trees
        self.sampling = sampling
        self.sample_rate = sample_rate
        self.seed = seed
        tree_kwargs.setdefault("attr_selection_strategy", "randomNotUsedYet")
        self.tree_kwargs = tree_kwargs
        self.trees: List[DecisionPathList] = []
        self.class_values = schema.class_values()
        self._evaluator: Optional[DevicePathEvaluator] = None

    def _sample(self, n: int) -> Tuple[np.ndarray, int, Dict]:
        """([T, R, LANES] int32: how often each tree's sample holds each row,
        by the sampling rule of the class docstring; the largest of them;
        what the `forest.sample` span says of how they were made: `native`,
        `threads` that drew and, where the walk counted them, `rejected`)."""
        rng = np.random.default_rng(self.seed)
        ws = np.zeros((self.num_trees, -(-n // LANES) * LANES), np.int32)
        walk = (ingest.bootstrap_counts_native(rng, n, ws)
                if self.sampling == "withReplace" and ingest.native_available()
                else None)
        if walk is not None:
            return to_lines(ws), walk.max_weight, dict(
                native=True, threads=walk.threads, rejected=walk.rejected)

        def count(t: int, idx: np.ndarray) -> None:
            ws[t, :n] = np.bincount(idx, minlength=n)

        # the written rule: the draws stay on the caller's thread, in tree
        # order; counting a finished draw runs on a worker while the next
        # is drawn
        with ThreadPoolExecutor(_SAMPLE_WORKERS) as pool:
            pending = []
            for t in range(self.num_trees):
                if self.sampling == "withReplace":
                    pending.append(pool.submit(count, t, rng.integers(0, n, n)))
                elif self.sampling == "withoutReplace":
                    ws[t, :n] = rng.random(n) < self.sample_rate
                else:
                    ws[t, :n] = 1
            for job in pending:
                job.result()
        return (to_lines(ws), int(ws.max(initial=1)),
                dict(native=False, threads=1))

    def fit(self, ds: Dataset) -> "RandomForestBuilder":
        """All trees grow together, one batched device call per level:
        trees share the (segment matrix, labels) upload and differ only in
        bootstrap weights and leaf routing, so the whole forest costs
        max_depth histogram+advance dispatches instead of
        num_trees x (max_depth x 2 + 1) round trips."""
        n = len(ds)
        self.trees = []
        self._evaluator = None
        builders = [
            DecisionTreeBuilder(self.schema, seed=self.seed + t,
                                **self.tree_kwargs)
            for t in range(self.num_trees)
        ]
        with obs.span("tree.fit", rows=n, trees=self.num_trees,
                      splits=len(builders[0].splits),
                      row_block=min(ROW_BLOCK, n)) as note:
            with obs.span("tree.segments",
                          **_segments_note(builders[0].splits)):
                seg_d = segment_matrix(builders[0].splits, ds)
                labels = to_lines(ds.labels())
            with obs.span("tree.put"):
                issued = obs.now()
                labels_d = jnp.asarray(labels)
                leaf_ids = jnp.zeros((self.num_trees,) + labels.shape,
                                     jnp.int32)
            obs.landed("tree.put.landed", (labels_d, leaf_ids), issued)
            with obs.span("forest.sample", sampling=self.sampling) as how:
                ws, heaviest, made = self._sample(n)
                how.update(made)
            with obs.span("tree.put"):
                issued = obs.now()
                ws_d = jnp.asarray(ws)
            obs.landed("tree.put.landed", ws_d, issued)
            digits = _weight_digits(heaviest)
            del labels, ws
            self.trees = _grow_forest(builders, seg_d, labels_d, ws_d,
                                      leaf_ids, digits, note)
        return self

    def predict(self, ds: Dataset, device: bool = False) -> np.ndarray:
        """Majority vote across trees. device=True routes every row
        through every tree's paths as one batched kernel
        (DevicePathEvaluator) instead of the host per-path loop."""
        if device:
            if self._evaluator is None:
                self._evaluator = DevicePathEvaluator(
                    self.trees, self.schema, self.class_values)
            return self._evaluator.predict(ds)
        k = len(self.class_values)
        votes = np.zeros((len(ds), k), np.int64)
        rows = np.arange(len(ds), dtype=np.int32)
        for tree in self.trees:
            pred = tree.predict(ds, self.class_values)
            votes[rows, pred] += 1
        return votes.argmax(axis=1).astype(np.int32)

    def validate(self, ds: Dataset, pos_class: int = 1) -> ConfusionMatrix:
        cm = ConfusionMatrix(self.class_values, pos_class=pos_class)
        cm.add(ds.labels(), self.predict(ds))
        return cm


class DataPartitioner:
    """Physically partition rows by the best candidate split — the dap.* MR
    job (tree/DataPartitioner.java:59-131): pick the top split of the given
    (or best) attribute, then write each segment's rows to
    `<base>/split=<splitId>/segment=<j>/data` files for the next pipeline
    stage."""

    def __init__(self, schema: FeatureSchema, algorithm: str = "giniIndex",
                 split_attribute: Optional[int] = None,
                 cat_partition_cap: int = 128):
        self.schema = schema
        self.algorithm = algorithm
        self.split_attribute = split_attribute
        self.cat_partition_cap = cat_partition_cap

    def best_split(self, ds: Dataset) -> Tuple[CandidateSplit, float]:
        from avenir_tpu.models.explore import ClassPartitionGenerator

        attrs = ([self.split_attribute]
                 if self.split_attribute is not None else None)
        cpg = ClassPartitionGenerator(ds, attributes=attrs,
                                      algorithm=self.algorithm,
                                      cat_partition_cap=self.cat_partition_cap)
        return cpg.best_split()

    def partition(self, ds: Dataset, base_path: str,
                  delim: str = ",") -> List[str]:
        """Returns the written `.../segment=j/data` file paths in segment
        order (empty segments still get an empty file, as one reducer per
        segment would)."""
        split, _ = self.best_split(ds)
        seg = split.segment_of(np.asarray(ds.column(split.attribute)))
        paths = []
        for j in range(split.n_segments):
            d = os.path.join(base_path, f"split={split.split_id}",
                             f"segment={j}")
            os.makedirs(d, exist_ok=True)
            p = os.path.join(d, "data")
            sub = ds.take(np.nonzero(seg == j)[0])
            with open(p, "w") as fh:
                fh.write(sub.to_csv(delim) if len(sub) else "")
            paths.append(p)
        return paths
