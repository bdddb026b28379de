"""The check of a decision-tree build (`decTree`): the model file each
timed job wrote, a `DecisionPathList` in JSON, against the rows the input
module drew. Found by `reference.kind` in the configuration's file.
Imports nothing of the program.

  model_bad             model files that do not parse, hold one path or
                        none, or state a predicate this check cannot read
  unstable_bytes        bytes that differ between a job's model file and
                        the warm-up job's (every job reads the same file)
  population_gap        rows by which the paths' `population` differ from
                        the rows that satisfy each path's predicates, plus
                        the rows that the paths together miss or count twice
  class_share_gap_max   the largest distance from a path's `classValPr` to
                        the class shares of the rows that satisfy it

The plain reference is the predicate itself, applied to whole columns.
It holds a model to the data it was built from; which split is best is
the next deployment's check to add.
"""

import json

import numpy as np

from chipbench import compare, generate

#: the numbers of which the lower-precision control has to fail one
CONTROL_FAILS = ("class_share_gap_max",)
_OPS = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
        "ge": np.greater_equal}


def sizes(cell, inputs):
    return {"n": len(inputs.y), "d": len(inputs.fields)}


def rows_of(path, fields, codes):
    """The rows [n] bool that satisfy every predicate of one path."""
    at = {f["ordinal"]: j for j, f in enumerate(fields)}
    keep = np.ones(len(codes), bool)
    for pred in path.get("predicates") or []:
        j = at[pred["attribute"]]
        if pred["operator"] == "in":
            names = fields[j]["cardinality"]
            keep &= np.isin(codes[:, j], [names.index(v) for v in
                                          pred["categoricalValues"]])
        else:
            keep &= _OPS[pred["operator"]](codes[:, j], pred["valueInt"])
    return keep


def compare_model(model, fields, codes, y, classes):
    """The numbers of one model against the columns it was built from."""
    covered = np.zeros(len(codes), np.int64)
    pop_gap, share_gap = 0, 0.0
    for path in model["decisionPaths"]:
        keep = rows_of(path, fields, codes)
        covered += keep
        pop_gap += abs(int(path["population"]) - int(keep.sum()))
        shares = np.bincount(y[keep], minlength=len(classes)) / max(keep.sum(), 1)
        said = [path["classValPr"].get(c, 0.0) for c in classes]
        share_gap = max(share_gap, float(np.max(np.abs(shares - said))))
    return pop_gap + int(np.abs(covered - 1).sum()), share_gap


def numbers(cell, inputs, seed, jobs, warm_out):
    out = {"model_bad": 0, "unstable_bytes": 0, "population_gap": 0,
           "class_share_gap_max": 0.0, "paths": 0}
    with open(warm_out, "rb") as fh:
        warm = fh.read()
    for job in (j for j in jobs if j["ok"]):
        with open(job["out"], "rb") as fh:
            blob = fh.read()
        out["unstable_bytes"] += compare.unstable_bytes(warm, blob)
        try:
            model = json.loads(blob)
            if len(model["decisionPaths"]) < 2:
                raise ValueError("a tree of one path has split nothing")
            pop, share = compare_model(model, inputs.fields, inputs.codes,
                                       inputs.y, inputs.classes)
        except (ValueError, KeyError, TypeError):
            out["model_bad"] += 1
            continue
        out["paths"] = len(model["decisionPaths"])
        out["population_gap"] += pop
        out["class_share_gap_max"] = max(out["class_share_gap_max"], share)
    return out


def to_bfloat16(x):
    """`x` cut to bfloat16's eight bits of mantissa."""
    bits = np.asarray(x, np.float32).view(np.uint32) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def control_numbers(cell, seed, jobs, dtype="bfloat16"):
    """The numbers a run would compare, had the program answered with the
    reference's own statistics of a one-split tree (the `int` field at the
    middle of its range) with the class shares kept in `dtype`: one
    precision below the float32 the configuration states."""
    cfg = cell.config
    fields = generate.load_module(
        cell.bench_dir, "inputs", cfg["inputs_kind"]).feature_fields(cfg["schema"])
    codes, y = generate.load_module(
        cell.bench_dir, "generators", cfg["generator"]["kind"]).draw(
            generate.seed_for(seed, 0), int(cfg["train_rows"]),
            cfg["generator"], fields)
    classes = list(cfg["generator"]["classes"])
    num = next(f for f in fields if f["dataType"] == "int")
    mid = (num["min"] + num["max"]) // 2
    paths = []
    for op in ("lt", "ge"):
        path = {"predicates": [{"attribute": num["ordinal"], "operator": op,
                                "valueInt": mid}]}
        keep = rows_of(path, fields, codes)
        shares = np.bincount(y[keep], minlength=len(classes)) / keep.sum()
        if dtype == "bfloat16":
            shares = to_bfloat16(shares)
        path.update(population=int(keep.sum()),
                    classValPr=dict(zip(classes, map(float, shares))))
        paths.append(path)
    pop, share = compare_model({"decisionPaths": paths}, fields, codes, y,
                               classes)
    return {"model_bad": 0, "unstable_bytes": 0, "population_gap": pop,
            "class_share_gap_max": share, "paths": len(paths)}
