"""The inputs of a job that reads one file and writes a model file, such
as `decTree`: one CSV made from the seed, the schema and the properties;
no test files. Found by `inputs_kind` in the configuration's file.

The row follows the schema's ordinals: the id, each feature field (a
categorical field as its value's name, an `int` field as the whole number)
and the class. The general writer `generate.make_csv` writes numeric
feature columns only, so this module writes its own; at a test's size a
join of string columns does, a deployment's module writes in bulk.

Every job of the window reads the same file (`n_files` is 1), so every
output has to equal the warm-up job's byte for byte.
"""

import json
import os

import numpy as np

from chipbench import generate


def feature_fields(schema):
    """The fields flagged `feature`, by ordinal, categorical ones too."""
    return sorted((f for f in schema["fields"] if f.get("feature")),
                  key=lambda f: f["ordinal"])


def text_column(field, codes):
    """A column's values as the file spells them."""
    if field["dataType"] == "categorical":
        return np.asarray(field["cardinality"])[codes]
    return codes.astype(str)


class Inputs:
    out_suffix = ".json"
    n_files = 1

    def __init__(self, cell, seed, work):
        cfg = cell.config
        gen, schema = cfg["generator"], cfg["schema"]
        self.fields = feature_fields(schema)
        self.classes = list(gen["classes"])
        n = int(cfg["train_rows"])
        # codes [n, d]: a categorical field's index into its cardinality,
        # an int field's number; y [n]: index into classes
        self.codes, self.y = generate.load_module(
            cell.bench_dir, "generators", gen["kind"]).draw(
                generate.seed_for(seed, 0), n, gen, self.fields)
        ids = np.char.add(gen["id_prefix"],
                          np.char.zfill(np.arange(n).astype(str), 8))
        cols = [ids] + [text_column(f, self.codes[:, j])
                        for j, f in enumerate(self.fields)]
        cols.append(np.asarray(self.classes)[self.y])
        self.train_path = os.path.join(work, "train.csv")
        with open(self.train_path, "w") as fh:
            fh.write("\n".join(map(",".join, zip(*cols))) + "\n")
        self.schema_path = os.path.join(work, "schema.json")
        with open(self.schema_path, "w") as fh:
            json.dump(schema, fh)
        self.props_path = os.path.join(work, "job.properties")
        with open(self.props_path, "w") as fh:
            for key, val in cfg["properties"].items():
                fh.write(f"{key}={val.format(schema=self.schema_path)}\n")
        self.job = cfg["job"]

    def argv(self, file_no, out):
        return [self.job, "--conf", self.props_path, self.train_path, out]

    def warmup_argv(self, out):
        return self.argv(0, out)
