"""The check of a random-forest build (`randomForest`): the directory of
`tree-NNN.json` files each timed job wrote, every tree a `DecisionPathList`
in JSON, against the plain reference (`forest_reference.py`) over all rows
and all trees. Found by `reference.kind` in the configuration's file.
Imports nothing of the program.

  model_bad            jobs whose output is not the forest the properties
                       ask for: files missing, surplus or unparsable; a
                       tree of one path or none; a predicate that is no
                       candidate split's; an attribute twice on a path; a
                       path deeper than the limit
  unstable_bytes       bytes that differ between the warm-up job's forest
                       and the window's first job's (same input file)
  rows_unrouted        per tree, rows that satisfy no path or more than one
  population_gap       per tree and path, `population` against the sum of
                       the tree's bootstrap counts over the rows that
                       satisfy the path, in int64, summed
  class_share_gap_max  the largest distance from a path's `classValPr` to
                       the weighted class shares of those rows
  split_not_best       internal nodes (rebuilt from the paths' prefixes)
                       whose split's weighted impurity, from the
                       reference's exact counts, is more than 1e-9 above
                       the least over the candidate splits of the same
                       attribute

The bootstrap counts are the reference's own, by the sampling rule the
job's contract states (`forest_reference.bootstrap_weights`); which
attributes a node drew is not replayed: the check holds what any draw must
satisfy. Every number is a sum over rows, so the rows are first compacted
to the distinct (features, class) rows there can be, each tree's counts
summed by cell (`forest_reference.compact`): 19,232 weighted rows stand
for 50M, exactly. Every job of the window reads the same file, so a forest
whose bytes equal one already compared is not compared again.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import compare, generate
from chipbench import forest_reference as ref

#: the numbers of which the lower-precision control has to fail one
CONTROL_FAILS = ("population_gap", "class_share_gap_max")
BEST_TOL = 1e-9
SEED = 0                             # the job states no seed: the program's


def sizes(cell, inputs) -> Dict:
    """The semantic sizes of the traced job: rows, trees, candidate splits,
    their segments, classes, and the level passes it ran (the deepest
    path of the forests compared, and the pass for the final counts)."""
    sem = ref.job_semantics(cell.config["properties"])
    splits = ref.candidate_splits(cell.config["schema"])
    return {"n": len(inputs.y), "trees": sem["trees"], "splits": len(splits),
            "segments": max(s["segments"] for s in splits),
            "classes": len(inputs.classes),
            "levels": getattr(inputs, "levels_seen", sem["max_depth"]) + 1}


class Table:
    """The cell's rows compacted, with every tree's bootstrap counts."""

    def __init__(self, schema: Dict, codes, y, classes: int, sem: Dict,
                 weights: Optional[np.ndarray] = None):
        fields = ref.feature_fields(schema)
        radix = [len(f["cardinality"]) if f["dataType"] == "categorical"
                 else int(f["max"]) + 1 for f in fields]
        cells = int(np.prod(radix)) * classes
        cell, self.codes, self.y = ref.compact(codes, y, radix, classes)
        self.rows = ref.sum_by_cell(cell, None, cells)       # unweighted
        if weights is None:
            weights = ref.bootstrap_weights(SEED, len(y), sem["trees"],
                                            sem["sampling"], sem["rate"])
        self.weights = np.stack([
            ref.sum_by_cell(cell, weights[t], cells)
            for t in range(sem["trees"])])
        self.schema, self.classes = schema, classes
        self.splits = ref.candidate_splits(schema)
        self.split_of = {p: s for s in self.splits for p in s["predicates"]}


def read_forest(folder: str) -> bytes:
    """The forest's files in name order, each after its name, as one
    blob (what `unstable_bytes` compares); empty where there is none."""
    if not os.path.isdir(folder):
        return b""
    parts = []
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name), "rb") as fh:
            parts.append(name.encode() + b"\n" + fh.read())
    return b"\n".join(parts)


def parse_forest(folder: str, sem: Dict, table: Table) -> List[List[Dict]]:
    """The forest's trees as lists of {"preds", "population", "shares"};
    raises ValueError, KeyError or TypeError on whatever `model_bad`
    counts."""
    want = [f"tree-{t:03d}.json" for t in range(sem["trees"])]
    if sorted(os.listdir(folder)) != want:
        raise ValueError("the forest's files are not tree-000.json .. "
                         f"tree-{sem['trees'] - 1:03d}.json")
    forest = []
    for name in want:
        with open(os.path.join(folder, name)) as fh:
            model = json.load(fh)
        if len(model["decisionPaths"]) < 2:
            raise ValueError("a tree of one path has split nothing")
        tree = []
        for path in model["decisionPaths"]:
            preds = tuple(ref.predicate_key(p)
                          for p in path.get("predicates") or [])
            attrs = [p[1] for p in preds]
            if len(preds) > sem["max_depth"] or len(set(attrs)) < len(attrs):
                raise ValueError("a path too deep or an attribute twice")
            if any(p not in table.split_of for p in preds):
                raise ValueError("a predicate that is no candidate split's")
            tree.append({"preds": preds, "population": int(path["population"]),
                         "shares": dict(path["classValPr"])})
        forest.append(tree)
    return forest


def node_split(children: List[Tuple], table: Table) -> Optional[Dict]:
    """The candidate split an internal node used, from the predicates its
    listed children begin with (an empty child has no path)."""
    fits = [s for s in table.splits
            if all(c in s["predicates"] for c in children)]
    return fits[0] if fits else None


def compare_tree(tree: List[Dict], t: int, table: Table, class_names,
                 algorithm: str) -> Dict:
    """One tree's numbers against the table's rows weighted for tree t."""
    codes, y, w = table.codes, table.y, table.weights[t]
    out = {"rows_unrouted": 0, "population_gap": 0,
           "class_share_gap_max": 0.0, "split_not_best": 0}
    routed = np.zeros(len(y), np.int64)
    nodes: Dict[Tuple, set] = {}
    for path in tree:
        keep = ref.rows_of(path["preds"], table.schema, codes)
        routed += keep
        counts = ref.class_counts(y, w, keep, table.classes)
        out["population_gap"] += abs(path["population"] - int(counts.sum()))
        shares = counts / max(int(counts.sum()), 1)
        said = np.array([path["shares"].get(c, 0.0) for c in class_names])
        out["class_share_gap_max"] = max(
            out["class_share_gap_max"], float(np.max(np.abs(shares - said))))
        for j, pred in enumerate(path["preds"]):
            nodes.setdefault(path["preds"][:j], set()).add(pred)
    out["rows_unrouted"] = int((table.rows * (routed != 1)).sum())
    for prefix, children in nodes.items():
        used = node_split(sorted(children, key=repr), table)
        if used is None:
            out["split_not_best"] += 1
            continue
        keep = ref.rows_of(prefix, table.schema, codes)
        score = {id(s): ref.weighted_impurity(
            ref.split_counts(s, codes, y, w, keep, table.classes), algorithm)
            for s in table.splits if s["attribute"] == used["attribute"]}
        out["split_not_best"] += score[id(used)] > min(score.values()) + BEST_TOL
    return out


def compare_forest(folder: str, sem: Dict, table: Table, class_names
                   ) -> Optional[Dict]:
    """One job's forest against the table, every tree; nothing where the
    forest is not what the properties ask for (`model_bad`)."""
    try:
        forest = parse_forest(folder, sem, table)
    except (OSError, ValueError, KeyError, TypeError):
        return None
    got = [compare_tree(tree, t, table, class_names, sem["algorithm"])
           for t, tree in enumerate(forest)]
    out = {k: (max if k.endswith("_max") else sum)(g[k] for g in got)
           for k in got[0]}
    out["paths"] = sum(len(tree) for tree in forest)
    out["depth"] = max(len(p["preds"]) for tree in forest for p in tree)
    return out


def numbers(cell, inputs, seed, jobs, warm_out) -> Dict:
    cfg = cell.config
    sem = ref.job_semantics(cfg["properties"])
    table = Table(cfg["schema"], inputs.codes, inputs.y, len(inputs.classes),
                  sem)
    out = {"model_bad": 0, "unstable_bytes": 0, "rows_unrouted": 0,
           "population_gap": 0, "class_share_gap_max": 0.0,
           "split_not_best": 0, "paths": 0, "forests_compared": 0}
    done = [j for j in jobs if j["ok"]]
    if done:
        out["unstable_bytes"] = compare.unstable_bytes(
            read_forest(warm_out), read_forest(done[0]["out"]))
    seen: Dict[bytes, Optional[Dict]] = {}
    for job in done:
        blob = read_forest(job["out"])
        if blob not in seen:
            seen[blob] = compare_forest(job["out"], sem, table, inputs.classes)
            out["forests_compared"] += 1
        one = seen[blob]
        if one is None:
            out["model_bad"] += 1
            continue
        out["paths"] = one["paths"]
        inputs.levels_seen = one["depth"]
        for k in ("rows_unrouted", "population_gap", "split_not_best"):
            out[k] += int(one[k])
        out["class_share_gap_max"] = max(out["class_share_gap_max"],
                                         one["class_share_gap_max"])
    return out


# ----------------------------------------------------------------- control
def to_bfloat16(x):
    """`x` cut to bfloat16's eight bits of mantissa."""
    bits = np.asarray(x, np.float32).view(np.uint32) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def float32_in_row_order(w: np.ndarray) -> float:
    """The sum a float32 accumulator reaches adding `w` row by row: past
    2^24 it no longer counts every row."""
    return float(np.cumsum(w.astype(np.float32), dtype=np.float32)[-1]) \
        if len(w) else 0.0


def control_numbers(cell, seed, jobs, dtype="bfloat16") -> Dict:
    """The numbers a run would compare, had the program answered with the
    reference's own forest, each path's class counts accumulated in
    float32 in row order over all rows and its shares kept in `dtype`: one
    precision below the configuration's integer counts and float64
    impurity. Draws the cell's rows at the cell's own size; runs no job."""
    cfg = cell.config
    sem = ref.job_semantics(cfg["properties"])
    fields = ref.feature_fields(cfg["schema"])
    gen = cfg["generator"]
    classes = list(gen["classes"])
    n = int(cfg["train_rows"])
    codes, y = generate.load_module(
        cell.bench_dir, "inputs", cfg["inputs_kind"]).draw_features(
            generate.load_module(cell.bench_dir, "generators", gen["kind"]),
            seed, n, gen, fields)
    weights = ref.bootstrap_weights(SEED, n, sem["trees"], sem["sampling"],
                                    sem["rate"])
    table = Table(cfg["schema"], codes, y, len(classes), sem, weights)
    forest = ref.grow_forest(table.codes, table.y, table.weights,
                             cfg["schema"], len(classes), sem, SEED)
    out = {"model_bad": 0, "unstable_bytes": 0, "rows_unrouted": 0,
           "population_gap": 0, "class_share_gap_max": 0.0,
           "split_not_best": 0, "paths": 0}
    for t, paths in enumerate(forest):
        tree = []
        for path in paths:
            keep = ref.rows_of(path["predicates"], cfg["schema"], codes)
            counts = np.array([float32_in_row_order(
                weights[t][keep & (y == c)]) for c in range(len(classes))],
                np.float32)
            shares = counts / max(counts.sum(dtype=np.float32), np.float32(1))
            if dtype == "bfloat16":
                shares = to_bfloat16(shares)
            tree.append({"preds": path["predicates"],
                         "population": int(counts.sum(dtype=np.float32)),
                         "shares": dict(zip(classes, map(float, shares)))})
        got = compare_tree(tree, t, table, classes, sem["algorithm"])
        out["paths"] += len(tree)
        for k in ("rows_unrouted", "population_gap", "split_not_best"):
            out[k] += int(got[k])
        out["class_share_gap_max"] = max(out["class_share_gap_max"],
                                         got["class_share_gap_max"])
    return out
